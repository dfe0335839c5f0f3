"""Plan cache: skip parse->analyze->plan->optimize for repeated shapes.

Reference parity: the reference pays the full planning pipeline per
statement and avoids it protocol-side with PREPARE/EXECUTE (the planned
io.trino query plan cache never landed upstream; Presto forks ship one
keyed on the canonical statement). Here planning is pure Python against
static catalogs, so on a TPU engine whose kernels are already shared
across literal variants (expr/hoist.py), re-planning is the last
per-statement cost a repeated query shape pays — exactly the "millions
of users, repeated query shapes" hot path.

Keying: entries key on the statement's canonical literal-free FINGERPRINT
(the AST skeleton with literal leaves masked) plus the masked literal
values, catalog/schema context, the session's current_date, bound
parameter types, and the plan-affecting session properties. For plain
SQL the values ride in the key — a plan may legally specialize on literal
values (constant folding, value-dependent conjunct extraction), so only
an identical statement reuses it. For EXECUTE ... USING the prepared
statement's `?` markers plan as value-free `BoundParam` leaves, the
values component is empty, and every re-execution with new parameters —
any values, same types — is a HIT: bind + dispatch, zero planning.

Consistency: entries record the tables their plan scans or writes;
DDL/DML against a table (CREATE/DROP/INSERT/CTAS) invalidates every entry
referencing it, so a cached plan never outlives the table handles or
statistics it was planned against. The cache is per-runner (it caches
handles resolved against that runner's catalogs) and shared with its
`for_query()` clones — the server's executor pool — under a lock, with
LRU bounds from the `plan_cache_max_entries` session property.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import weakref
from typing import Any, Dict, FrozenSet, Hashable, List, Optional, Tuple

# process-lifetime counters across every runner's cache (obs/metrics.py
# exports these as trino_tpu_plan_cache_* gauges, like the jit cache's)
_STATS = {"hits": 0, "misses": 0, "evictions": 0, "invalidations": 0}
_STATS_LOCK = threading.Lock()
# live caches, for the resident-entries gauge
_INSTANCES: "weakref.WeakSet[PlanCache]" = weakref.WeakSet()

DEFAULT_MAX_ENTRIES = 256

# session properties that feed the logical planner / optimizer; anything
# read at LOWERING time (hoist_literals, page capacities, spill
# thresholds, dynamic filtering) applies per execution and must NOT
# fragment the key
PLAN_PROPERTIES = ("join_distribution_type", "join_reordering_strategy",
                   "join_broadcast_threshold_rows", "distributed_sort",
                   "partitioned_agg_min_ndv")

TableKey = Tuple[str, str, str]   # (catalog, schema, table)


class _GenerationGuard:
    """The put-generation race discipline every table-keyed cache layer
    shares (plan cache here; result/scan caches in serve/caches.py):
    `generation()` snapshots BEFORE the work whose output will be
    cached; `put` rejects when any referenced table was invalidated
    since — so a value computed against pre-change state can never land
    after the invalidation that should have dropped it. Single-sourced
    here so a fix to the discipline cannot silently miss one cache."""

    def _init_generations(self) -> None:
        self._gen = 0
        self._invalidated_at: Dict[TableKey, int] = {}

    def generation(self) -> int:
        """Snapshot taken BEFORE planning/executing; hand it to `put`
        so a value built against pre-invalidation state never lands."""
        with self._lock:
            return self._gen

    def _bump_generation_locked(self, table: TableKey) -> None:
        self._gen += 1
        self._invalidated_at[table] = self._gen

    def _stale_locked(self, tables, gen: Optional[int]) -> bool:
        return gen is not None and any(
            self._invalidated_at.get(tk, 0) > gen for tk in tables)


@dataclasses.dataclass
class PlanEntry:
    plan: Any                       # the optimized OutputNode
    tables: FrozenSet[TableKey]     # referenced tables, for invalidation


class PlanCache(_GenerationGuard):
    """LRU of optimized plans with table-keyed invalidation.

    `max_entries` is a property of the CACHE, set by the runner that owns
    it (from its session's `plan_cache_max_entries`) — never by
    `for_query()` clones, whose sessions carry per-request header
    overrides: one client shrinking the bound must not evict every other
    session's warm plans from the shared cache."""

    def __init__(self, max_entries: int = DEFAULT_MAX_ENTRIES):
        self._lock = threading.RLock()
        self._entries: "collections.OrderedDict[Hashable, PlanEntry]" = \
            collections.OrderedDict()
        self.max_entries = max_entries
        # invalidation generations (_GenerationGuard): `invalidate` can
        # only drop entries already PRESENT, but a planner that started
        # before a concurrent DDL/INSERT may put its (stale) plan
        # afterwards — so `put` carries the generation read before
        # planning and is rejected if any referenced table was
        # invalidated since
        self._init_generations()
        # invalidation fan-out (trino_tpu/serve/caches.py): the result
        # and scan caches register here so the ONE invalidate() call a
        # DDL/INSERT drives evicts plans, cached answers, and staged
        # scan pages together — no cache can outlive a table change
        self._hooks: List = []
        _INSTANCES.add(self)

    def add_invalidation_hook(self, fn) -> None:
        with self._lock:
            if fn not in self._hooks:
                self._hooks.append(fn)

    def get(self, key: Hashable) -> Optional[Any]:
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                _count("misses")
                return None
            self._entries.move_to_end(key)
            _count("hits")
            return entry.plan

    def put(self, key: Hashable, plan: Any, tables: FrozenSet[TableKey],
            gen: Optional[int] = None) -> None:
        if self.max_entries <= 0:
            return
        with self._lock:
            if self._stale_locked(tables, gen):
                # a referenced table changed while this plan was being
                # built: its handles/statistics are pre-change, and the
                # invalidation that should have dropped it already ran
                return
            self._entries[key] = PlanEntry(plan, frozenset(tables))
            self._entries.move_to_end(key)
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
                _count("evictions")

    def resize(self, max_entries: int) -> None:
        """Apply a new LRU bound NOW: shrinking evicts immediately, so a
        lowered bound reclaims plans even under a hit-only steady-state
        workload (put()'s eviction loop never runs on hits)."""
        with self._lock:
            self.max_entries = max_entries
            while len(self._entries) > max(self.max_entries, 0):
                self._entries.popitem(last=False)
                _count("evictions")

    def invalidate(self, table: TableKey) -> int:
        """Drop every entry whose plan references `table` (DDL/INSERT
        against it changed handles, data, or statistics)."""
        with self._lock:
            self._bump_generation_locked(table)
            stale = [k for k, e in self._entries.items()
                     if table in e.tables]
            for k in stale:
                del self._entries[k]
            hooks = list(self._hooks)
        if stale:
            _count("invalidations", len(stale))
        for fn in hooks:    # outside the lock: hooks take their own
            fn(table)
        return len(stale)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


def _count(name: str, n: int = 1) -> None:
    with _STATS_LOCK:
        _STATS[name] += n


def stats() -> Dict[str, int]:
    """Process-lifetime counters + resident entries across live caches."""
    with _STATS_LOCK:
        out = dict(_STATS)
    out["entries"] = sum(len(c) for c in list(_INSTANCES))
    return out


# ------------------------------------------------- statement fingerprints


def statement_fingerprint(stmt) -> Tuple[Hashable, Tuple]:
    """(canonical skeleton, literal values) for a statement AST.

    The skeleton is the statement with every literal leaf masked to its
    node kind — the literal-free canonical form shared by all literal
    variants of one query shape (and BY CONSTRUCTION by a prepared
    statement's `?` markers, which carry no values at all). The values
    tuple restores exactness: a plain statement's plan key is
    (skeleton, values), a prepared statement's is (skeleton, ()).
    """
    from trino_tpu.sql import tree as t

    literal_kinds = (t.LongLiteral, t.DoubleLiteral, t.DecimalLiteral,
                     t.StringLiteral, t.DateLiteral, t.TimestampLiteral,
                     t.BooleanLiteral, t.IntervalLiteral)
    values: List[Tuple] = []

    def walk(x):
        if isinstance(x, literal_kinds):
            values.append(tuple(
                getattr(x, f.name) for f in dataclasses.fields(x)))
            return (type(x).__name__, "?")
        if dataclasses.is_dataclass(x) and isinstance(x, t.Node):
            return (type(x).__name__,) + tuple(
                walk(getattr(x, f.name))
                for f in dataclasses.fields(x))
        if isinstance(x, (tuple, list)):
            return tuple(walk(item) for item in x)
        return x   # str/int/bool/None/enum field values
    return walk(stmt), tuple(values)


def plan_tables(plan) -> FrozenSet[TableKey]:
    """Tables a plan scans or writes, as invalidation keys. Handles carry
    schema.table (ConnectorTableHandle.name); the node carries the
    catalog."""
    from trino_tpu.planner.nodes import TableScanNode, TableWriterNode

    out = set()

    def walk(node):
        if isinstance(node, (TableScanNode, TableWriterNode)):
            st = node.table.name
            out.add((node.catalog, st.schema, st.table))
        for s in node.sources:
            walk(s)
    walk(plan)
    return frozenset(out)
