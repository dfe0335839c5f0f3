"""Device-resident hot-table cache: columns that stay in HBM across
queries.

The serving-tier scan cache (serve/caches.ScanCache) keeps STAGED PAGES
per (table, columns, capacity) — a re-scan with a different capacity or
column subset re-stages from the host. This tier caches the COLUMNS
themselves: full-length device arrays promoted once, then served to any
scan over any subset of the cached columns at any page capacity — the
local dispatch loop wraps them in pages by device-side slicing, and mesh
`shard_map` staging shards them by row range, so a warm repeated scan
does ZERO host->device transfers (counter-proven via the per-query
`scan_staging_bytes` counter, like `exchanges_fused`).

Admission is scan-frequency x size: a (table, columns) working set
becomes a promotion candidate after `table_cache_min_scans` scans, and
eviction under the byte budget drops the entry with the lowest
frequency x recency score first — one giant cold table cannot wipe a
hot dashboard's dimension tables. Residency is accounted against the
per-chip node pool (exec/memory.NodeMemoryPool.reserve_cache): the pool
declines admission that would overflow the chip's HBM budget, and the
per-device residency gauges surface in /v1/metrics and
system.runtime.nodes.

Invalidation rides the PlanCache hook fan-out: ONE DDL/INSERT call
drops cached plans, result sets, staged scan pages, AND the device
columns — a resident column can never outlive a table change.

Sharded entries (`ShardedTable`, the mesh runner's form of residency):
chip i of the runner's mesh holds the rows of ITS splits, made on chip i
(`mesh_exec.read_shard_pages`), as one array per column sharded over the
`workers` axis. A co-scheduled mesh program is handed those arrays as they
are — no slice, no copy between chips, nothing from the host — and the
dispatch loop reads its shard's block. They are made by the server's
`tables:` warm-up on a mesh runner and by a mesh scan's own promotion, and
admitted PER CHIP against the node pool (`reserve_cache(shard bytes,
device=i)`, sized from the chip's measured HBM): `table_cache_max_bytes`
bounds the full-length entries of one chip, as it always did, and says
nothing about a table spread over several.

Like the other serving caches, one instance per owning runner, shared
with `for_query()` clones under a lock.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

from trino_tpu.exec.plan_cache import _GenerationGuard

TableKey = Tuple[str, str, str]   # (catalog, schema, table)

DEFAULT_MAX_BYTES = 1 << 30
DEFAULT_MIN_SCANS = 2

# process-lifetime counters across every runner's cache (metrics gauges
# + system.runtime.caches)
_STATS = {"hits": 0, "misses": 0, "promotions": 0, "evictions": 0,
          "invalidations": 0, "admission_denied": 0}
_STATS_LOCK = threading.Lock()
_INSTANCES: "weakref.WeakSet[TableCache]" = weakref.WeakSet()


def _count(name: str, n: int = 1) -> None:
    with _STATS_LOCK:
        _STATS[name] += n


def _next_pow2(n: int) -> int:
    out = 8
    while out < n:
        out *= 2
    return out


@dataclasses.dataclass
class ResidentTable:
    """One promoted working set: full-length device columns (capacity =
    pow2(rows)) for a set of column names of one table."""

    table: TableKey
    columns: Dict[str, object]      # name -> page.Column (device arrays)
    rows: int
    nbytes: int
    device: Optional[int]
    freq: int = 0
    last_used: float = 0.0

    def score(self) -> Tuple[int, float]:
        """Eviction order: lowest frequency first, LRU within a tie."""
        return (self.freq, self.last_used)


@dataclasses.dataclass
class ShardedTable:
    """Columns of one table resident across a mesh: every array has the
    mesh as its leading axis, block i on chip i."""

    table: TableKey
    n_shards: int
    columns: Dict[str, object]      # name -> page.Column, values (n, cap)
    num_rows: object                # (n,) int32, sharded like the columns
    rows: int
    shard_bytes: int                # on each chip
    freq: int = 0
    last_used: float = 0.0

    @property
    def capacity(self) -> int:
        """Lanes of one shard."""
        return next(iter(self.columns.values())).values.shape[1]

    def page(self, column_names: Sequence[str]):
        """The workers-sharded global Page a mesh program takes."""
        from trino_tpu.page import Page
        return Page(tuple(self.columns[n] for n in column_names),
                    self.num_rows)

    def shard_page(self, column_names: Sequence[str], shard: int):
        """Shard `shard`'s page, on its chip (the dispatch loop)."""
        import jax
        import jax.numpy as jnp

        def block(x):
            mine = next(s for s in x.addressable_shards
                        if (s.index[0].start or 0) == shard)
            return jnp.squeeze(mine.data, axis=0)
        return jax.tree_util.tree_map(block, self.page(column_names))


class TableCache(_GenerationGuard):
    def __init__(self, max_bytes: int = DEFAULT_MAX_BYTES,
                 min_scans: int = DEFAULT_MIN_SCANS):
        self._lock = threading.RLock()
        self.max_bytes = int(max_bytes)
        self.min_scans = int(min_scans)
        self.resident_bytes = 0     # full-length entries: max_bytes' side
        self.sharded_bytes = 0      # over all chips; the node pool's side
        # key = (table, frozenset of column names)
        self._entries: Dict[tuple, ResidentTable] = {}
        # key = (table, mesh size): one sharded entry per table, its
        # column set growing as more of the table is warmed
        self._sharded: Dict[tuple, ShardedTable] = {}
        # scan-frequency ledger feeding admission (kept separate from
        # entries: a candidate earns its promotion before it costs HBM)
        self._scan_counts: Dict[tuple, int] = {}
        # put-generation race guard (exec/plan_cache._GenerationGuard,
        # the discipline every table-keyed cache layer here shares): a
        # promotion built from pages scanned BEFORE a concurrent
        # INSERT's invalidation must not land AFTER it — callers
        # snapshot generation() before the scan and pass it to
        # promote_from_pages
        self._init_generations()
        _INSTANCES.add(self)

    # ------------------------------------------------------------ probes

    def configure(self, max_bytes: int, min_scans: int) -> None:
        """Session-driven sizing (the OWNING runner applies its
        table_cache_max_bytes/min_scans per query; clones never do)."""
        with self._lock:
            self.min_scans = int(min_scans)
            if int(max_bytes) != self.max_bytes:
                self.max_bytes = int(max_bytes)
                self._evict_to_budget_locked()

    def note_scan(self, table: TableKey,
                  column_names: Sequence[str]) -> int:
        """Record one scan of (table, columns); returns the running
        count — the executor promotes when it reaches min_scans."""
        key = (table, frozenset(column_names))
        with self._lock:
            n = self._scan_counts.get(key, 0) + 1
            self._scan_counts[key] = n
            return n

    def should_promote(self, table: TableKey,
                       column_names: Sequence[str]) -> bool:
        """Not already resident (the caller owns the frequency check —
        it reads the session's table_cache_min_scans)."""
        with self._lock:
            return (table, frozenset(column_names)) not in self._entries \
                and self._find_locked(table, column_names) is None

    def _find_locked(self, table: TableKey,
                     column_names: Sequence[str]
                     ) -> Optional[ResidentTable]:
        """An entry serving ALL requested columns (exact set or a
        superset promoted for a wider scan)."""
        want = set(column_names)
        exact = self._entries.get((table, frozenset(want)))
        if exact is not None:
            return exact
        for (tk, cols), entry in self._entries.items():
            if tk == table and want <= cols:
                return entry
        return None

    def lookup(self, table: TableKey, column_names: Sequence[str],
               count: bool = True) -> Optional[ResidentTable]:
        """Resident entry covering the requested columns, or None.
        `count=True` counts hit/miss and bumps the recency/frequency
        score; count=False is the secondary-shard probe (a mesh scan
        counts once, on shard 0)."""
        with self._lock:
            entry = self._find_locked(table, column_names)
            if entry is None:
                if count:
                    _count("misses")
                return None
            if count:
                entry.freq += 1
                entry.last_used = time.monotonic()
                _count("hits")
            return entry

    def peek(self, table: TableKey, column_names: Sequence[str]) -> bool:
        """lookup() without counters (eligibility probes)."""
        with self._lock:
            return self._find_locked(table, column_names) is not None

    # ------------------------------------------------------------ sharded

    def lookup_sharded(self, table: TableKey, column_names: Sequence[str],
                       n_shards: int, count: bool = True
                       ) -> Optional[ShardedTable]:
        """The table's entry over a mesh of `n_shards` if it holds every
        requested column, else None (a miss is counted by the
        full-length probe that follows it)."""
        with self._lock:
            entry = self._sharded.get((table, n_shards))
            if entry is None or not set(column_names) <= set(entry.columns):
                return None
            if count:
                entry.freq += 1
                entry.last_used = time.monotonic()
                _count("hits")
            return entry

    def admit_sharded(self, table: TableKey, column_names: Sequence[str],
                      page, rows: int, collector=None,
                      gen: Optional[int] = None) -> bool:
        """Keep a workers-sharded global Page (block i already on chip i)
        as the table's resident shards: no copy is made. Admission is per
        chip, against the node pool; columns join the table's entry when
        their shards line up with it, else they replace it."""
        from trino_tpu.exec.memory import NODE_POOL
        n = int(page.num_rows.shape[0])
        if any(c.lengths is not None for c in page.columns):
            return False    # list layouts: as for full-length entries
        with self._lock:
            if self._stale_locked((table,), gen):
                return False
            key = (table, n)
            old = self._sharded.get(key)
            if old is not None and (old.rows != rows or old.capacity
                                    != page.columns[0].values.shape[1]):
                self._release_sharded_locked(self._sharded.pop(key))
                old = None
            columns = dict(old.columns) if old is not None else {}
            fresh = {name: c for name, c in zip(column_names, page.columns)
                     if name not in columns}
            need = sum(c.nbytes for c in fresh.values()) // n
            reserved = []
            for shard in range(n):
                if not NODE_POOL.reserve_cache(need, shard):
                    for done in reserved:
                        NODE_POOL.free_cache(need, done)
                    _count("admission_denied")
                    return False
                reserved.append(shard)
            columns.update(fresh)
            self._sharded[key] = ShardedTable(
                table, n, columns,
                page.num_rows if old is None else old.num_rows, rows,
                need + (old.shard_bytes if old is not None else 0),
                freq=1 + (old.freq if old is not None else 0),
                last_used=time.monotonic())
            self.sharded_bytes += need * n
            _count("promotions")
        self._span(collector, "table-cache-promote", table=table,
                   bytes=need * n, rows=rows, columns=len(fresh), shards=n)
        return True

    def _release_sharded_locked(self, entry: ShardedTable) -> None:
        from trino_tpu.exec.memory import NODE_POOL
        self.sharded_bytes -= entry.shard_bytes * entry.n_shards
        for shard in range(entry.n_shards):
            NODE_POOL.free_cache(entry.shard_bytes, shard)

    # ---------------------------------------------------------- promotion

    def promote_from_pages(self, table: TableKey,
                           symbols_cols: Sequence[Tuple[str, object]],
                           pages: Sequence, counts: Sequence[int],
                           device: Optional[int] = None,
                           collector=None,
                           gen: Optional[int] = None) -> bool:
        """Build full-length device columns from already-staged scan
        pages (they are ON DEVICE — promotion costs device concats, not
        a host re-read) and admit them under the budget + node pool.
        `gen` is the generation snapshot taken BEFORE the pages were
        scanned: a promotion racing a concurrent INSERT's invalidation
        is rejected rather than landing stale columns."""
        import jax.numpy as jnp

        from trino_tpu.page import Column

        names = [n for n, _ in symbols_cols]
        rows = int(sum(int(c) for c in counts))
        if rows <= 0:
            return False
        live = [(p, int(c)) for p, c in zip(pages, counts) if int(c) > 0]
        columns: Dict[str, object] = {}
        cap = _next_pow2(rows)
        # what the entry would hold, from shapes alone: a table that
        # cannot be admitted (SF10 lineitem against 1 GiB) must not pay
        # a full-length copy of every column on every scan first
        if not self._fits(_entry_bytes(
                live[0][0], rows, [any(p.columns[i].valid is not None
                                       for p, _ in live)
                                   for i in range(len(names))])):
            _count("admission_denied")
            return False
        for i, (name, ch) in enumerate(symbols_cols):
            cols = [p.columns[i] for p, _ in live]
            dicts = {c.dictionary.fingerprint for c in cols
                     if c.dictionary is not None}
            if len(dicts) > 1:
                return False    # per-page pools diverge: codes unstable
            if any(c.lengths is not None for c in cols):
                return False    # list layouts: not worth the plumbing
            vals = jnp.concatenate([c.values[:n]
                                    for c, (_, n) in zip(cols, live)])
            if vals.shape[0] < cap:
                pad = jnp.zeros((cap - vals.shape[0],) + vals.shape[1:],
                                dtype=vals.dtype)
                vals = jnp.concatenate([vals, pad])
            valid = None
            if any(c.valid is not None for c in cols):
                valid = jnp.concatenate(
                    [c.valid_mask()[:n] for c, (_, n) in zip(cols, live)])
                if valid.shape[0] < cap:
                    valid = jnp.concatenate(
                        [valid, jnp.zeros(cap - valid.shape[0],
                                          dtype=bool)])
            columns[name] = Column(vals, valid, ch.type,
                                   cols[0].dictionary)
        nbytes = sum(c.nbytes for c in columns.values())
        return self._admit(ResidentTable(table, columns, rows, nbytes,
                                         device, freq=1,
                                         last_used=time.monotonic()),
                           frozenset(names), collector, gen)

    def _fits(self, need: int) -> bool:
        with self._lock:
            # what the entry would score once admitted: used once, now
            return need <= self.max_bytes and self._room_locked(
                need, (1, time.monotonic()))

    def admits(self, page, rows: int) -> bool:
        """Whether `rows` rows of columns shaped like `page`'s could be
        admitted now, from shapes alone: asked on a scan's first page,
        before its pages are gathered for `promote_from_pages` — a page a
        connector cut from its own resident columns is a copy, and a
        table's worth of them kept for a promotion that will be refused
        is the table twice."""
        return rows > 0 and self._fits(_entry_bytes(
            page, rows, [c.valid is not None for c in page.columns]))

    def _admit(self, entry: ResidentTable, colset: frozenset,
               collector=None, gen: Optional[int] = None) -> bool:
        from trino_tpu.exec.memory import NODE_POOL
        with self._lock:
            if self._stale_locked((entry.table,), gen):
                # the table changed while these pages were being
                # scanned: the invalidation that should have dropped
                # them already ran (same race guard as PlanCache.put)
                return False
            if entry.nbytes > self.max_bytes:
                _count("admission_denied")
                return False
            key = (entry.table, colset)
            old = self._entries.pop(key, None)
            if old is not None:
                self._release_locked(old)
            # budget first, then the chip's pool: a declined pool
            # reservation (HBM pressure from live queries) wins
            if not self._room_locked(entry.nbytes, entry.score()):
                _count("admission_denied")
                return False
            self._evict_to_budget_locked(incoming=entry.nbytes)
            if not NODE_POOL.reserve_cache(entry.nbytes, entry.device):
                _count("admission_denied")
                return False
            self._entries[key] = entry
            self.resident_bytes += entry.nbytes
            _count("promotions")
        self._span(collector, "table-cache-promote", table=entry.table,
                   bytes=entry.nbytes, rows=entry.rows,
                   columns=len(colset))
        return True

    # ----------------------------------------------------------- eviction

    def _release_locked(self, entry: ResidentTable) -> None:
        from trino_tpu.exec.memory import NODE_POOL
        self.resident_bytes -= entry.nbytes
        NODE_POOL.free_cache(entry.nbytes, entry.device)

    def _room_locked(self, incoming: int, score: Tuple[int, float]) -> bool:
        """Whether `incoming` bytes fit once every entry that scores below
        `score` is gone. An entry earns its place: a newcomer pushes out
        what is colder than itself and nothing hotter, so a working set
        the size of the whole budget (SF10: two lineitem columns at their
        pow2 envelope are 1 GiB to the byte) is refused while the tables
        every query reads hold it, instead of wiping them on its second
        scan and being wiped by the next query's set in turn — each
        round a full-length copy, and a new page shape to compile for."""
        colder = sum(e.nbytes for e in self._entries.values()
                     if e.score() < score)
        return self.resident_bytes - colder + incoming <= self.max_bytes

    def _evict_to_budget_locked(self, incoming: int = 0) -> None:
        while (self.resident_bytes + incoming > self.max_bytes
               and self._entries):
            key = min(self._entries,
                      key=lambda k: self._entries[k].score())
            victim = self._entries.pop(key)
            self._release_locked(victim)
            _count("evictions")

    def invalidate(self, table: TableKey) -> int:
        """PlanCache hook target: drop every resident column of the
        changed table (and its admission history — the post-change table
        must re-earn residency with fresh data)."""
        with self._lock:
            self._bump_generation_locked(table)
            stale = [k for k in self._entries if k[0] == table]
            for k in stale:
                self._release_locked(self._entries.pop(k))
            sharded = [k for k in self._sharded if k[0] == table]
            for k in sharded:
                self._release_sharded_locked(self._sharded.pop(k))
            stale += sharded
            for k in [k for k in self._scan_counts if k[0] == table]:
                del self._scan_counts[k]
        if stale:
            _count("invalidations", len(stale))
        return len(stale)

    def clear(self) -> None:
        with self._lock:
            for entry in self._entries.values():
                self._release_locked(entry)
            for entry in self._sharded.values():
                self._release_sharded_locked(entry)
            self._entries.clear()
            self._sharded.clear()
            self._scan_counts.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries) + len(self._sharded)

    # -------------------------------------------------------------- spans

    @staticmethod
    def _span(collector, name: str, **attrs) -> None:
        if collector is None:
            return
        try:
            with collector.span(name, kind="table-cache", **attrs):
                pass
        except Exception:
            pass


def _entry_bytes(page, rows: int, has_valid: Sequence[bool]) -> int:
    """Bytes of a full-length entry of `rows` rows of `page`'s columns."""
    cap = _next_pow2(rows)
    return sum(
        cap * (c.values.dtype.itemsize * math.prod(c.values.shape[1:])
               + bool(valid))
        for c, valid in zip(page.columns, has_valid))


def build_pages(entry: ResidentTable, column_names: Sequence[str],
                cap: int) -> List:
    """Scan pages over a resident entry: one zero-copy page when the
    whole table fits the scan capacity (rows <= cap — the common case,
    scan capacities grow to the table's row envelope), device-side
    slices otherwise. Never touches the host."""
    import jax.numpy as jnp

    from trino_tpu.page import Column, Page
    cols = [entry.columns[n] for n in column_names]
    rows = entry.rows
    if rows <= cap:
        return [Page(tuple(cols), rows)]
    pages = []
    off = 0
    pcap = _next_pow2(cap)
    while off < rows:
        n = min(cap, rows - off)
        sliced = []
        for c in cols:
            vals = c.values[off:off + pcap]
            if vals.shape[0] < pcap:
                vals = jnp.concatenate(
                    [vals, jnp.zeros((pcap - vals.shape[0],)
                                     + vals.shape[1:], dtype=vals.dtype)])
            valid = None
            if c.valid is not None:
                valid = c.valid[off:off + pcap]
                if valid.shape[0] < pcap:
                    valid = jnp.concatenate(
                        [valid, jnp.zeros(pcap - valid.shape[0],
                                          dtype=bool)])
            sliced.append(Column(vals, valid, c.type, c.dictionary))
        pages.append(Page(tuple(sliced), n))
        off += cap
    return pages


def build_shard_page(entry: ResidentTable, column_names: Sequence[str],
                     shard: int, n_shards: int) -> Optional[object]:
    """One shard's slice only (the dispatch-loop path: each shard
    executor materializes just its own row range)."""
    pages = build_shard_pages(entry, column_names, n_shards,
                              only_shard=shard)
    return pages[shard]


def build_shard_pages(entry: ResidentTable, column_names: Sequence[str],
                      n_shards: int,
                      only_shard: Optional[int] = None
                      ) -> List[Optional[object]]:
    """Per-shard pages for mesh staging: shard s holds row range
    [split_range(rows, s, n)) of the resident columns — device-side
    slices (a cross-device placement is an ICI copy, never host bytes)."""
    import jax.numpy as jnp

    from trino_tpu.connector.spi import split_range
    from trino_tpu.page import Column, Page
    cols = [entry.columns[n] for n in column_names]
    rows = entry.rows
    spans = [split_range(rows, s, n_shards) for s in range(n_shards)]
    pcap = _next_pow2(max(max((e - s) for s, e in spans), 1))
    out: List[Optional[object]] = []
    for idx, (s, e) in enumerate(spans):
        n = e - s
        if n <= 0 or (only_shard is not None and idx != only_shard):
            out.append(None)
            continue
        sliced = []
        for c in cols:
            vals = c.values[s:s + pcap]
            if vals.shape[0] < pcap:
                vals = jnp.concatenate(
                    [vals, jnp.zeros((pcap - vals.shape[0],)
                                     + vals.shape[1:], dtype=vals.dtype)])
            valid = None
            if c.valid is not None:
                valid = c.valid[s:s + pcap]
                if valid.shape[0] < pcap:
                    valid = jnp.concatenate(
                        [valid, jnp.zeros(pcap - valid.shape[0],
                                          dtype=bool)])
            sliced.append(Column(vals, valid, c.type, c.dictionary))
        out.append(Page(tuple(sliced), n))
    return out


def table_cache_stats() -> Dict[str, int]:
    """Process counters + residency across live caches (metrics gauges
    and the system.runtime.caches 'table' row)."""
    with _STATS_LOCK:
        out = dict(_STATS)
    caches = list(_INSTANCES)
    out["entries"] = sum(len(c) for c in caches)
    out["bytes"] = sum(c.resident_bytes + c.sharded_bytes for c in caches)
    return out


def device_residency() -> Dict[Optional[int], int]:
    """bytes resident per device across live caches (the per-chip
    residency gauge; None = default device)."""
    out: Dict[Optional[int], int] = {}
    for cache in list(_INSTANCES):
        with cache._lock:
            for entry in cache._entries.values():
                out[entry.device] = out.get(entry.device, 0) + entry.nbytes
            for entry in cache._sharded.values():
                for shard in range(entry.n_shards):
                    out[shard] = out.get(shard, 0) + entry.shard_bytes
    return out
