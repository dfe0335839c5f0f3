"""Query + node memory accounting with a low-memory killer.

Reference parity: memory/MemoryPool.java:44 + lib/trino-memory-context
(AggregatedMemoryContext tree) + memory/ClusterMemoryManager.java with
memory/TotalReservationLowMemoryKiller.java — accounting is hierarchical:
every blocking materialization (join build side, aggregation/sort/window
collect, exchange buffers) reserves its page bytes against the query's
`query_max_memory` ledger AND the process-wide `NodeMemoryPool`. A
reservation that would overflow the query limit fails the query with the
reference's "Query exceeded per-node memory limit" error; one that would
overflow the NODE pool invokes the low-memory killer, which picks a victim
query by policy (`total-reservation`: the largest ledger) and fails it with
CLUSTER_OUT_OF_MEMORY — retryable, so retry_policy=QUERY re-runs the
victim once the pressure clears.

TPU framing: the pool models one chip's HBM, the scarce resource a fused
streaming pipeline does NOT consume (pages flow through one kernel) but
blocking operators do. Reservations are tracked per operator tag so errors
name the offender, and freed when an operator's output is consumed
(operator scopes call free()). At query end the ledger must read zero; a
nonzero ledger on a successful query is a reservation LEAK, surfaced as a
query warning and counted on the pool (system.runtime.nodes).
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Optional, Tuple

from trino_tpu.errors import (CLUSTER_OUT_OF_MEMORY,
                              EXCEEDED_LOCAL_MEMORY_LIMIT, TrinoError)


class ExceededMemoryLimitError(TrinoError, RuntimeError):
    """io.trino.ExceededMemoryLimitException analog (RuntimeError kept in
    the bases for pre-taxonomy callers)."""

    CODE = EXCEEDED_LOCAL_MEMORY_LIMIT


class ClusterOutOfMemoryError(TrinoError, RuntimeError):
    """The low-memory killer's verdict: this query was selected (or timed
    out waiting for a victim's release) when a reservation would overflow
    the NODE pool. Retryable — re-running after the pressure clears may
    succeed (ClusterMemoryManager kill + FTE retry contract)."""

    CODE = CLUSTER_OUT_OF_MEMORY


@contextlib.contextmanager
def degrade_to_spill(session):
    """Graceful degradation for a fragment retry after an
    ExceededMemoryLimitError / ClusterOutOfMemoryError: force the spill
    path on and pull every spill threshold under the memory limit, so
    blocking operators flush to host partitions instead of materializing
    over-limit device pages (TaskExecutor's revoke-memory-then-retry
    analog). Restores the session's property bag on exit."""
    saved = dict(session.properties)
    limit = int(session.get("query_max_memory"))
    threshold = max(1, limit // 4)
    session.properties["spill_enabled"] = True
    for prop in ("join_spill_threshold_bytes", "agg_spill_threshold_bytes",
                 "sort_spill_threshold_bytes"):
        session.properties[prop] = min(int(session.get(prop)), threshold)
    try:
        yield
    finally:
        session.properties.clear()
        session.properties.update(saved)


def _fmt_bytes(n: int) -> str:
    units = ("B", "kB", "MB", "GB", "TB")
    v = float(n)
    for u in units:
        if abs(v) < 1024 or u == units[-1]:
            return f"{int(v)}{u}" if u == "B" else f"{v:.2f}{u}"
        v /= 1024
    return f"{n}B"


def page_bytes(page) -> int:
    """Device bytes of one Page (sum of Column.nbytes)."""
    return sum(col.nbytes for col in page.columns)


def live_page_bytes(page, rows: int) -> int:
    """Data bytes of the LIVE rows of a Page: pages are capacity-padded
    (Page.filter keeps its input capacity), so raw Column.nbytes measures
    padding too — stats counters must scale to the live row count or a
    2-row selective result reports megabytes."""
    cap = max(int(page.capacity), 1)
    return page_bytes(page) * int(rows) // cap


class NodeMemoryPool:
    """Process-wide reservation pool all queries share (MemoryPool.java +
    ClusterMemoryManager collapsed to the single-node case).

    `limit` is the node's reservable byte budget (None = unbounded — the
    engine's default, since tests and direct runners size their own
    queries). When a reservation would overflow the pool, the low-memory
    killer picks a victim by `killer_policy`:

      total-reservation  kill the query with the largest ledger
                         (TotalReservationLowMemoryKiller)
      none               never kill; the requester fails

    The victim is marked killed (it raises ClusterOutOfMemoryError at its
    next reservation or cooperative checkpoint) and the requester WAITS for
    the victim's unwind to release bytes, up to its `wait_s`; a timeout
    fails the requester with the same retryable error.
    """

    def __init__(self, limit_bytes: Optional[int] = None,
                 killer_policy: str = "total-reservation"):
        self._cond = threading.Condition()
        self.limit = limit_bytes
        self.killer_policy = killer_policy
        self.reserved = 0
        self.peak = 0
        self.kills = 0          # victims selected by the killer
        self.leaks = 0          # successful queries that ended nonzero
        self.leaked_bytes = 0
        # where the limit came from: "default" (unbounded / hand-set) or
        # "measured" (sized from the backend's reported per-device memory
        # minus the scan-cache budget at startup — autosize_node_pool)
        self.budget_source = "default"
        # when True (set by autosize_node_pool), `limit` is ONE chip's
        # HBM budget and device-hinted reservations are enforced against
        # THAT chip's running total — a mesh query staging n shards must
        # not trip a single-chip limit with the cross-chip sum. Hand-set
        # limits (tests, chaos harnesses, explicit server config) keep
        # the historical global-sum enforcement.
        self.enforce_per_device = False
        # per-chip accounting: reservations carrying a device hint (mesh
        # shard executors, sharded staging) attribute bytes to the chip
        # that holds them. The pool `limit` models ONE chip's HBM, so the
        # per-device gauges are what say whether any single chip is near
        # its budget. Advisory after attempt rollbacks (like by_tag).
        self.device_reserved: Dict[int, int] = {}
        self.device_peak: Dict[int, int] = {}
        # HBM pinned by cross-query caches (the device-resident table
        # cache, exec/table_cache.py): tracked SEPARATELY from query
        # reservations — cache residency outlives queries, so it must
        # not trip the per-query leak detector — but counted against
        # the pool limit at admission time, so a cache can never pin
        # HBM a live query's reservation was promised
        self.cache_reserved = 0
        self.device_cache_reserved: Dict[int, int] = {}
        self._contexts: Dict[str, "QueryMemoryContext"] = {}

    # ------------------------------------------------------- configuration

    def set_limit(self, limit_bytes: Optional[int]) -> None:
        with self._cond:
            self.limit = limit_bytes
            self._cond.notify_all()

    @contextlib.contextmanager
    def limited(self, limit_bytes: Optional[int],
                killer_policy: Optional[str] = None):
        """Scoped pool reconfiguration (tests / chaos harnesses)."""
        with self._cond:
            saved = (self.limit, self.killer_policy)
            self.limit = limit_bytes
            if killer_policy is not None:
                self.killer_policy = killer_policy
            self._cond.notify_all()
        try:
            yield self
        finally:
            with self._cond:
                self.limit, self.killer_policy = saved
                self._cond.notify_all()

    # -------------------------------------------------------- registration

    def register(self, ctx: "QueryMemoryContext") -> None:
        with self._cond:
            self._contexts[ctx.query_id] = ctx

    def unregister(self, ctx: "QueryMemoryContext") -> None:
        with self._cond:
            if self._contexts.get(ctx.query_id) is ctx:
                del self._contexts[ctx.query_id]
            self._cond.notify_all()

    def reserved_of(self, query_id: str) -> int:
        ctx = self._contexts.get(query_id)
        return ctx.reserved if ctx is not None else 0

    # ----------------------------------------------------------- the pool

    def acquire(self, ctx: "QueryMemoryContext", nbytes: int, tag: str,
                wait_s: float, device: Optional[int] = None) -> None:
        """Grant `nbytes` to `ctx` or raise ClusterOutOfMemoryError.

        Runs the low-memory killer when the pool would overflow; blocks
        (releasing the pool lock) while a marked victim unwinds."""
        deadline: Optional[float] = None
        with self._cond:
            while True:
                if ctx.kill_reason is not None:
                    raise ClusterOutOfMemoryError(ctx.kill_reason)
                if self.enforce_per_device and device is not None:
                    # per-chip budget: this chip's total is what the
                    # limit bounds (the global sum spans n chips' HBM)
                    current = self.device_reserved.get(device, 0)
                else:
                    current = self.reserved
                if self.limit is None or current + nbytes <= self.limit:
                    self.reserved += nbytes
                    self.peak = max(self.peak, self.reserved)
                    if device is not None:
                        d = self.device_reserved.get(device, 0) + nbytes
                        self.device_reserved[device] = d
                        self.device_peak[device] = max(
                            self.device_peak.get(device, 0), d)
                    return
                # kill at most ONE victim per pressure event: while a
                # marked victim still holds bytes, spurious wakeups (any
                # unrelated free() notifies) must WAIT for its unwind,
                # not cascade-kill the rest of the fleet
                if not any(c.kill_reason is not None and c.reserved > 0
                           for c in self._contexts.values()):
                    if self.killer_policy == "none":
                        # never kill: the requester fails, and NO kill
                        # is recorded (pool_kills must read zero on a
                        # node whose killer is disabled)
                        raise ClusterOutOfMemoryError(
                            f"node memory pool exhausted (killer "
                            f"disabled): [{tag}] requested "
                            f"{_fmt_bytes(nbytes)} with "
                            f"{_fmt_bytes(self.reserved)}/"
                            f"{_fmt_bytes(self.limit)} reserved")
                    victim = self._select_victim_locked()
                    if victim is None or victim is ctx:
                        # the requester itself is the largest reservation
                        # (or nothing is killable): self-inflicted
                        # pressure — fail the requester; its retry
                        # re-runs with spill forced
                        self._kill_locked(ctx, nbytes, tag, ctx)
                        raise ClusterOutOfMemoryError(ctx.kill_reason)
                    self._kill_locked(victim, nbytes, tag, ctx)
                if deadline is None:
                    deadline = time.monotonic() + max(0.0, wait_s)
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not self._cond.wait(remaining):
                    raise ClusterOutOfMemoryError(
                        f"node memory pool exhausted: [{tag}] requested "
                        f"{_fmt_bytes(nbytes)} with {_fmt_bytes(self.reserved)}"
                        f"/{_fmt_bytes(self.limit)} reserved and no victim "
                        f"released within {wait_s:.1f}s")

    def release(self, nbytes: int, device: Optional[int] = None) -> None:
        if nbytes <= 0:
            return
        with self._cond:
            self.reserved = max(0, self.reserved - nbytes)
            if device is not None:
                self.device_reserved[device] = max(
                    0, self.device_reserved.get(device, 0) - nbytes)
            self._cond.notify_all()

    # ----------------------------------------------- cache residency

    def reserve_cache(self, nbytes: int,
                      device: Optional[int] = None) -> bool:
        """Admit `nbytes` of cross-query cache residency (the HBM table
        cache) against the pool budget. Never kills and never blocks —
        a cache that cannot fit simply isn't admitted (returns False);
        live queries always win the HBM."""
        nbytes = int(nbytes)
        if nbytes <= 0:
            return True
        with self._cond:
            if self.limit is not None:
                if self.enforce_per_device and device is not None:
                    current = (self.device_reserved.get(device, 0)
                               + self.device_cache_reserved.get(device, 0))
                else:
                    current = self.reserved + self.cache_reserved
                if current + nbytes > self.limit:
                    return False
            self.cache_reserved += nbytes
            key = device if device is not None else 0
            self.device_cache_reserved[key] = \
                self.device_cache_reserved.get(key, 0) + nbytes
            return True

    def free_cache(self, nbytes: int, device: Optional[int] = None) -> None:
        nbytes = int(nbytes)
        if nbytes <= 0:
            return
        with self._cond:
            self.cache_reserved = max(0, self.cache_reserved - nbytes)
            key = device if device is not None else 0
            self.device_cache_reserved[key] = max(
                0, self.device_cache_reserved.get(key, 0) - nbytes)
            self._cond.notify_all()

    def reset_context(self, ctx: "QueryMemoryContext") -> None:
        """Atomically drop ALL of a context's reservation and clear its
        kill mark (between retry attempts): a killed victim must hand
        back every byte the killer wanted — and the mark must clear
        under the pool lock so it can't race a concurrent re-kill."""
        with self._cond:
            delta = ctx.reserved
            ctx.reserved = 0
            ctx.kill_reason = None
            self.reserved = max(0, self.reserved - delta)
            for d, b in ctx.by_device.items():
                self.device_reserved[d] = max(
                    0, self.device_reserved.get(d, 0) - b)
            ctx.by_device.clear()
            self._cond.notify_all()

    # ---------------------------------------------------------- the killer

    def _select_victim_locked(self) -> Optional["QueryMemoryContext"]:
        if self.killer_policy == "none":
            return None
        # total-reservation: largest live ledger not already marked
        best = None
        for c in self._contexts.values():
            if c.kill_reason is not None or c.reserved <= 0:
                continue
            if best is None or c.reserved > best.reserved:
                best = c
        return best

    def _kill_locked(self, victim: "QueryMemoryContext", nbytes: int,
                     tag: str, requester: "QueryMemoryContext") -> None:
        if victim.kill_reason is not None:
            return
        victim.kill_reason = (
            f"Query killed because the node is out of memory (low-memory "
            f"killer, policy {self.killer_policy}): query "
            f"{requester.query_id} [{tag}] requested {_fmt_bytes(nbytes)} "
            f"with {_fmt_bytes(self.reserved)}/{_fmt_bytes(self.limit)} "
            f"reserved; victim {victim.query_id} held "
            f"{_fmt_bytes(victim.reserved)}. Please retry in a few minutes")
        victim.kills += 1
        self.kills += 1
        # wake the victim if it is itself blocked in acquire()
        self._cond.notify_all()

    def record_leak(self, nbytes: int) -> None:
        with self._cond:
            self.leaks += 1
            self.leaked_bytes += nbytes


# the process-wide pool (the single node's HBM budget; unbounded until a
# server/operator sizes it — LocalMemoryManager singleton scope)
NODE_POOL = NodeMemoryPool()


def measured_device_memory_bytes() -> Optional[int]:
    """The backend's reported per-device memory capacity (TPU HBM via
    device.memory_stats()['bytes_limit']); None on the CPU backend only
    (including the forced 8-device dev mesh), which reports none. An
    accelerator that fails to report its limit is an error, never a
    silent drop to the static CPU default."""
    import jax
    dev = jax.local_devices()[0]
    if dev.platform == "cpu":
        return None
    limit = int((dev.memory_stats() or {}).get("bytes_limit") or 0)
    if not limit:
        raise RuntimeError(
            f"{dev.platform} device {dev.device_kind!r} reports no "
            "bytes_limit in memory_stats(); cannot size the node pool")
    return limit


def autosize_node_pool(scan_cache_budget: Optional[int] = None,
                       pool: Optional[NodeMemoryPool] = None
                       ) -> Tuple[Optional[int], str]:
    """Size the node pool from the backend's MEASURED per-device memory
    at startup (replacing any hand-tuned constant): per-chip budget =
    measured HBM minus the connector scan-cache budget (the staged-column
    LRU owns that slice of HBM by design), floored at a quarter of the
    chip so a misconfigured cache budget can't zero the pool. Backends
    that don't report capacity (CPU) keep the current static default and
    return source "default". Returns (limit_bytes, source); the chosen
    budget and source surface in system.runtime.nodes and /v1/metrics."""
    pool = pool if pool is not None else NODE_POOL
    measured = measured_device_memory_bytes()
    if measured is None:
        pool.budget_source = "default"
        return pool.limit, "default"
    if scan_cache_budget is None:
        try:
            from trino_tpu.connector import tpch
            scan_cache_budget = int(tpch._DEVICE_COL_CACHE_BYTES)
        except Exception:
            scan_cache_budget = 0
    limit = max(measured - int(scan_cache_budget), measured // 4)
    pool.set_limit(limit)
    pool.budget_source = "measured"
    # the measured limit is PER-CHIP HBM: device-hinted reservations
    # (mesh shards) enforce against their chip's total, not the mesh sum
    pool.enforce_per_device = True
    return limit, "measured"


class QueryMemoryContext:
    """Single-query reservation ledger checked against query_max_memory,
    mirrored into a NodeMemoryPool when one is attached (the query level
    of the query→operator→node hierarchy; by_tag is the operator level).

    Mutations come from the query's own executor thread; the killer thread
    only writes `kill_reason`/`kills` under the pool lock."""

    _anon = 0

    def __init__(self, limit_bytes: Optional[int],
                 query_id: Optional[str] = None,
                 pool: Optional[NodeMemoryPool] = None,
                 wait_s: float = 2.0):
        self.limit = int(limit_bytes) if limit_bytes is not None else None
        self.reserved = 0
        self.peak = 0
        self.by_tag: Dict[str, int] = {}
        self.by_device: Dict[int, int] = {}
        if not query_id:
            QueryMemoryContext._anon += 1
            query_id = f"ctx_{QueryMemoryContext._anon}"
        self.query_id = query_id
        self.pool = pool
        self.wait_s = float(wait_s)
        self.kill_reason: Optional[str] = None
        self.kills = 0          # times this query was selected as victim
        if pool is not None:
            pool.register(self)

    def reserve(self, nbytes: int, tag: str = "operator",
                device: Optional[int] = None) -> None:
        nbytes = int(nbytes)
        if nbytes <= 0:
            return
        if self.kill_reason is not None:
            raise ClusterOutOfMemoryError(self.kill_reason)
        if self.limit is not None and self.reserved + nbytes > self.limit:
            raise ExceededMemoryLimitError(
                f"Query exceeded per-node memory limit of "
                f"{_fmt_bytes(self.limit)} [{tag} requested "
                f"{_fmt_bytes(nbytes)}, reserved "
                f"{_fmt_bytes(self.reserved)}]")
        if self.pool is not None:
            self.pool.acquire(self, nbytes, tag, self.wait_s, device)
        self.reserved += nbytes
        self.by_tag[tag] = self.by_tag.get(tag, 0) + nbytes
        if device is not None:
            self.by_device[device] = self.by_device.get(device, 0) + nbytes
        self.peak = max(self.peak, self.reserved)

    def free(self, nbytes: int, tag: str = "operator",
             device: Optional[int] = None) -> None:
        nbytes = int(nbytes)
        released = min(max(nbytes, 0), self.reserved)
        self.reserved -= released
        if tag in self.by_tag:
            self.by_tag[tag] = max(0, self.by_tag[tag] - nbytes)
        if device is not None:
            self.by_device[device] = max(
                0, self.by_device.get(device, 0) - nbytes)
        if self.pool is not None:
            self.pool.release(released, device)

    def poll(self) -> None:
        """Cooperative kill checkpoint: raise if the low-memory killer (or
        a `memory` fault site) marked this query."""
        if self.kill_reason is not None:
            raise ClusterOutOfMemoryError(self.kill_reason)

    def clear_kill(self) -> None:
        """Clear the kill mark under the pool lock (a task-scope retry is
        about to re-run): unlocked clearing could race a concurrent
        re-kill and leave a requester waiting on a victim that never
        unwinds."""
        if self.pool is not None:
            with self.pool._cond:
                self.kill_reason = None
                self.pool._cond.notify_all()
        else:
            self.kill_reason = None

    def rollback_to(self, mark: int) -> None:
        """Release everything reserved past `mark` back to the pool — a
        failed attempt's unfreed reservations must not stack across
        retries. (by_tag is advisory after a rollback: it names offenders
        in error messages, it is not the ledger.)"""
        delta = self.reserved - int(mark)
        if delta <= 0:
            return
        self.reserved = int(mark)
        if self.pool is not None:
            self.pool.release(delta)

    def reset_attempt(self) -> None:
        """Between retry attempts: drop the failed attempt's reservations
        and clear a kill mark so the re-run starts clean (all bytes go
        back to the pool — a killed victim releases what the killer was
        reclaiming, not just its latest task's delta)."""
        if self.pool is not None:
            self.pool.reset_context(self)
        else:
            self.reserved = 0
            self.kill_reason = None
        self.by_tag.clear()

    def close(self) -> int:
        """Query end: the ledger must read zero. Returns the leaked byte
        count (0 when clean), releases any remainder back to the pool, and
        unregisters from it."""
        leaked = self.reserved
        self.rollback_to(0)
        if self.pool is not None:
            self.pool.unregister(self)
        return leaked
