"""Per-query stats pipeline: QueryStatsCollector + OperatorStats.

Reference parity: execution/QueryStats.java (query-level rollup: planning
vs execution wall, raw input/output, spilled bytes) +
operator/OperatorStats.java (per-operator wall time, positions, bytes,
rolled up by PlanNodeStatsSummarizer for EXPLAIN ANALYZE). The collector
is created once per query by the runner and threaded through the local
planner, the distributed scheduler, and the jit cache, so every surface —
EXPLAIN ANALYZE, system.runtime.queries, event listeners, the
benchmark — reports the SAME numbers.

Two collection levels, because per-operator instrumentation is not free
on this engine. Query-level collection (phases, output rows/bytes, jit
cache hits/misses, compile walls, spill bytes) is ALWAYS on;
operator-level collection turns on per query via the
`collect_operator_stats` session property or EXPLAIN ANALYZE. Since
round 13 operator-level collection NO LONGER splits fused kernel chains:
a chain records one measured device wall per dispatch
(`block_until_ready` at chain granularity only) and obs/profiler.py
apportions it across the chain's operators by XLA cost analysis — the
instrumented query executes the SAME executables as the plain one (the
jit cache stays warm across the toggle). Blocking operators are still
timed inclusively at their output boundary; under EXPLAIN ANALYZE
`fence` additionally pins their asynchronously dispatched device work
with `block_until_ready` (the OperationTimer discipline, TPU edition).

Device-time truth: under operator-level collection or EXPLAIN ANALYZE
the query is FENCED — the jit cache pins every kernel dispatch (chains,
joins, aggregates, sorts, mesh programs) with `block_until_ready` and
`device_time_ms` is their summed wall; `compile_time_ms` is the summed
wall of every XLA compile this query triggered (measured at the jit
cache's AOT compile sites, always on); `host_time_ms` = execution -
device - compile is what is left. Unfenced, device work runs
asynchronously and neither can be told from the execution wall: both
are null.

The request's life (round 25): the spans of REQUEST_SPANS in the query's
tree, stamped on `time.monotonic()` — `queued` (submit -> an executor
thread took it), `planning`, `execution`, and under execution `compile`
(one per AOT compile, per first call of a `cached_kernel`, and per
compile XLA reports outside both: `backend_compile`) and `result_fetch`
(first result page on the host -> last); on a mesh runner also
`mesh_stage` (a co-scheduled program's leaf scans being staged: nothing
of it once the shards are resident). snapshot() carries them as absolute
[name, start, end] triples, so spans of concurrent queries and a device
trace lay on one axis.

The executor's host timeline (PR 39): what the executor thread does
inside `execution` is named where it happens by `activity(name, detail)`
— `kernel_call`, `host_read`, `page_pull`, `page_concat`, `to_host`,
`rows_to_python`, `compile`, `lower_plan`, `eager_slice`, `like_table`
(ACTIVITIES;
README lists the sites). An activity is two clock reads into
`host_s`/`host_n` (self time: an activity nested in another is taken out
of the outer one) and, under a profiler session, a
`jax.profiler.TraceAnnotation("host__<name>[:<detail>]")`; a phase enters
`request__<name>` the same way. With no session the annotation is not
made and its name not built (`TraceAnnotation.is_enabled()`, the flag the
annotation itself would test); with one the interval lies in the xplane's
`/host:` plane on the executor thread's own line, on the device trace's
clock. What is
left of `execution` under no activity is the interpreter's: generators,
`Page` construction, this collector's bookkeeping. It is computed
(`benchmark/host_timeline.py`), never stamped. An activity never spans a
`yield`: a generator suspended inside one would put its consumer's work
under it.

Threading contract: one collector belongs to one query, mutated by that
query's executor thread only (distributed shards dispatch sequentially
on it); cross-thread readers consume the immutable snapshot() taken at
query end.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import numbers
import time
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from trino_tpu.obs.spans import Span


@dataclasses.dataclass
class OperatorStats:
    """One plan node's runtime counters (OperatorStats.java analog):
    output rows/pages/bytes + inclusive wall time; exclusive time and
    input rows derive from the child links at render/snapshot time."""

    node_id: int
    name: str
    output_rows: int = 0
    pages: int = 0
    output_bytes: int = 0
    wall_s: float = 0.0
    # measured device wall apportioned to this operator by the XLA cost
    # model (obs/profiler.py): the operator's share of its fused chain's
    # block_until_ready wall. Sums to the measured chain walls across a
    # query's operators — the device-attribution contract.
    device_s: float = 0.0
    # True when wall_s holds an EXCLUSIVE cost-model share (fused chain
    # entries, mesh program nodes) rather than the inclusive boundary
    # wall the counting wrapper measures — the renderer must not
    # subtract children from a share that never contained them
    fused: bool = False
    source_ids: Tuple[int, ...] = ()


REQUEST_SPANS = ("queued", "planning", "execution", "compile",
                 "result_fetch", "mesh_stage")
# the named host activities of an executor thread (the module docstring)
ACTIVITIES = ("kernel_call", "host_read", "page_pull", "page_concat",
              "to_host", "rows_to_python", "compile", "lower_plan",
              "eager_slice", "like_table")
# names of the programs XLA compiled for a query that `backend_compiled`
# keeps: the last few
_BACKEND_COMPILED_KEPT = 8
# what stands in where there is nobody to tell (reusable: it holds nothing)
NO_ACTIVITY = contextlib.nullcontext()
# whether a profiler session would keep an annotation made now: the flag a
# TraceAnnotation tests itself, asked before the object and its name exist
_profiling = TraceAnnotation.is_enabled


class _Activity:
    """One entry of `QueryStatsCollector.activity`. A class with slots and
    not a `@contextmanager`: the generator form costs a microsecond more
    an entry, and a chain dispatches a page through several."""

    __slots__ = ("_col", "_name", "_detail", "_annotation", "_closed_s",
                 "_t0")

    def __init__(self, col: "QueryStatsCollector", name: str,
                 detail: Optional[str]):
        self._col = col
        self._name = name
        self._detail = detail

    def __enter__(self):
        col = self._col
        # seconds of the activities that closed inside the one open now:
        # the outer one's so far is kept here, mine start at nothing
        self._closed_s = col._closed_s
        col._closed_s = 0.0
        self._annotation = TraceAnnotation(
            f"host__{self._name}" if self._detail is None
            else f"host__{self._name}:{self._detail}") \
            if _profiling() else None
        self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb):
        wall = time.monotonic() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        col, name = self._col, self._name
        col.host_s[name] += wall - col._closed_s
        col.host_n[name] += 1
        col._closed_s = self._closed_s + wall
        return False


class QueryStatsCollector:
    def __init__(self, query_id: str = "", operator_level: bool = False,
                 fence: bool = False, queued_at: Optional[float] = None,
                 dequeued_at: Optional[float] = None):
        """`queued_at`/`dequeued_at`: `time.monotonic()` when the server
        accepted the statement and when an executor thread took it; the
        query's tree then starts at the submit, with a `queued` span."""
        self.query_id = query_id
        self.operator_level = bool(operator_level)
        self.fence = bool(fence)
        self.root = Span(query_id or "query", kind="query")
        if queued_at is not None:
            self.root.start_s = float(queued_at)
            self.root.children.append(Span(
                "queued", kind="phase", start_s=float(queued_at),
                end_s=max(float(queued_at), dequeued_at
                          if dequeued_at is not None else time.monotonic())))
        self._stack: List[Span] = [self.root]
        self.phases: Dict[str, float] = {}
        self.operators: Dict[int, OperatorStats] = {}
        self.output_rows = 0
        self.output_bytes = 0
        self.spilled_bytes = 0
        self.jit_hits = 0
        self.jit_misses = 0
        # device-time truth (obs/profiler.py + exec/jit_cache):
        # device_time_s sums the measured walls of every kernel
        # dispatch of a fenced query (`fenced` below; unfenced it stays
        # 0.0 and reads null in the snapshot — device time then remains
        # folded into execution wall). compile_time_s sums the wall of
        # every XLA compile this query triggered, measured at the jit
        # cache's AOT compile sites with the compiled program's HLO
        # instruction count and cost-model flops/bytes alongside.
        self.device_time_s = 0.0
        self.compile_time_s = 0.0
        self.jit_compiles = 0
        self.compiled_hlo_ops = 0
        self.estimated_bytes = 0.0
        # hits on a canonical key whose literal parameter values differ
        # from that key's previous call — kernel sharing that per-literal
        # keying could not have expressed
        self.jit_param_hits = 0
        # plan cache consults (exec/plan_cache.py): a hit means this
        # query skipped parse->plan->optimize and re-ran a cached plan
        self.plan_cache_hits = 0
        self.plan_cache_misses = 0
        # serving-tier caches (trino_tpu/serve/caches.py): a result-cache
        # hit answered with zero planning/compiles/execution; a
        # scan-cache hit reused staged device pages for a table scan
        self.result_cache_hits = 0
        self.result_cache_misses = 0
        self.scan_cache_hits = 0
        self.scan_cache_misses = 0
        # device-resident table cache (exec/table_cache.py): a hit
        # served a scan entirely from HBM-resident columns; and the
        # data-plane proof for it — scan_staging_bytes counts every
        # host->device byte table scans staged this query (0 on a warm
        # cached scan, the `exchanges_fused`-style counter contract)
        self.table_cache_hits = 0
        self.table_cache_misses = 0
        self.scan_staging_bytes = 0
        # what of that a scan really moved host -> device (Column.
        # from_numpy under the page source): 0 when the source handed
        # back arrays already on the device (tpch's generated columns,
        # any connector-side device cache)
        self.scan_host_staging_bytes = 0
        # chain dispatches with a filter step (local_planner.
        # compose_chain): `deferred` ran a program whose filters hand a
        # selection mask to the partial aggregate and compact nothing,
        # `run` one whose filters compact the page
        self.compactions_deferred = 0
        self.compactions_run = 0
        # launches of a chain that walks its scan's pages inside its
        # program (a scan of resident columns into a direct aggregate),
        # and the pages walked in them: shapes, no sync
        self.chain_walks = 0
        self.chain_walk_pages = 0
        # compactions on the join's probe path, made by the host with
        # the kept count in hand (local_planner._compact_counted): `tight`
        # gathered the kept prefix alone at the count's pow2 capacity,
        # `full` ran Page.filter over the whole page, `skipped` moved
        # nothing because nothing was dropped. The lane sums are over the
        # compactions that ran: page capacities in, gather indices out —
        # their quotient is the share of lanes still gathered
        self.probe_compactions_tight = 0
        self.probe_compactions_full = 0
        self.probe_compactions_skipped = 0
        self.probe_compaction_lanes_in = 0
        self.probe_compaction_lanes_gathered = 0
        # a join's lookup (local_planner._prepare_probe, one decision a
        # build): `row_table` — a unique INNER build under the span limit,
        # one gather a probe lane against a table of build rows;
        # `position_table` — any other build under it, one gather against
        # a table of sorted positions, then run_len there; `set_table` —
        # a single-key SEMI, ANTI or MARK build under it, one gather
        # against a membership table scattered from the unsorted keys, no
        # sort at all; `search` — a searchsorted a buffer.
        # `probe_lookup_lanes` sums the capacities of the probe buffers
        # those lookups ran over
        self.probe_lookups_row_table = 0
        self.probe_lookups_position_table = 0
        self.probe_lookups_set_table = 0
        self.probe_lookups_search = 0
        # capacities of the build pages of the SEMI, ANTI and MARK joins
        # (shapes, no sync): those whose lanes went into the set table as
        # they arrived, and those that were sorted (`search`, a composite
        # key)
        self.semi_build_lanes_set = 0
        self.semi_build_lanes_sorted = 0
        self.probe_lookup_lanes = 0
        # ... and those of them that went through `search` (a key of
        # several columns is mix-hashed to 64 bits, so its span fits no
        # table: Q9's partsupp join)
        self.probe_lookup_lanes_search = 0
        # boolean LIKE tables built on the host for this query, one per
        # (dictionary, pattern) a chain (local_planner._like_tables): the
        # activity `like_table` times them
        self.like_tables_built = 0
        # probe pages whose join ran again at a larger output capacity
        # (local_planner._run_with_overflow and the FULL join's loop): the
        # true total exceeded `max(page_capacity, page.capacity)`, so the
        # first run's lookup and expansion were thrown away. Counted on
        # the host from the totals it reads anyway
        self.probe_overflow_reruns = 0
        # CROSS JoinNodes in the plans this query executed (runner): a
        # connected join graph plans none (optimizer.reorder_joins)
        self.cross_joins = 0
        # dispatches of a chain or mesh program (jit_cache.
        # profiled_kernel) whose direct GROUP BY (ops/aggregate.
        # _direct_aggregate) reduced its slot table lane-wise under slot
        # masks / with scatter-adds; a program holding both counts in both
        self.direct_reduces_masked = 0
        self.direct_reduces_scattered = 0
        # dispatches of a program (chain, mesh or `cached_kernel`: the
        # PARTIAL chain, the FINAL / INTERMEDIATE / SINGLE kernels) whose
        # sorted GROUP BY reduced its state columns by the segmented scan
        # (ops/aggregate._scan_reduce), and the capacities of the pages
        # those reduces ran over (shapes, no sync)
        self.sorted_reduces_scanned = 0
        self.sorted_reduce_lanes = 0
        # capacities of the pages a sorted GROUP BY (ops/aggregate.
        # _sorted_aggregate) found already in key order, and ran with no
        # sort and no gather, or had to sort: the device decides and says
        # which beside the page it returns (page.note_device), read with
        # the row counts at the snapshot (`count_rows`). A mesh program's
        # are counted in neither
        self.group_by_lanes_in_order = 0
        self.group_by_lanes_sorted = 0
        # lake connector pruning (connector/lake/): whole data files
        # and row groups skipped via partition values + min/max zone
        # maps evaluated against the scan's TupleDomain (static
        # pushdown and join dynamic filters alike)
        self.files_pruned = 0
        self.row_groups_pruned = 0
        # streaming delivery (trino_tpu/serve/streaming.py): chunks that
        # left through the result ring buffer. Output rows/bytes are
        # counted ONCE at the producer regardless of whether the result
        # was streamed, buffered, or served from the result cache.
        self.streamed_chunks = 0
        self.streamed_rows = 0
        self.retries = 0
        self.faults_injected = 0
        # inter-fragment exchange data plane (exec/mesh_exec.py +
        # exec/distributed.py): 'fused' exchanges ran as collectives
        # inlined in a co-scheduled mesh program; 'staged' exchanges ran
        # as standalone collectives over host-staged per-shard fragment
        # outputs (the fallback dispatch loop). Rows/bytes are live-row
        # estimates of what crossed the exchange.
        self.exchanges_fused = 0
        self.exchanges_staged = 0
        self.exchange_rows = 0
        self.exchange_bytes = 0
        # mesh shape the query executed over (0 = single-device)
        self.mesh_devices = 0
        # co-scheduled mesh programs (exec/mesh_exec.py): programs the
        # query ran, and dispatches of them — a round more for every
        # climb of the capacity ladder, so the two are equal once a
        # shape's capacities are remembered; bytes its mesh scans
        # generated, copied between chips or staged from the host (0 on
        # resident shards); literals and EXECUTE values that went in as
        # the programs' replicated operand instead of into their keys
        self.mesh_programs = 0
        self.mesh_program_rounds = 0
        self.mesh_scan_moved_bytes = 0
        self.mesh_params = 0
        # preemptible sliced execution (exec/sliced/): bounded-work
        # slices the query executed, operator checkpoints saved/restored
        # (restored > 0 on a retried query = the retry RESUMED instead
        # of re-running — slices re-executed < slices total), bytes
        # checkpointed, and the measured cancel-request -> unwind wall
        # when the query was preempted (0.0 = never preempted)
        self.slices_executed = 0
        self.checkpoints_saved = 0
        self.checkpoints_restored = 0
        self.checkpoint_bytes = 0
        self.preempt_latency_ms = 0.0
        # adaptive operator strategies (exec/adaptive.py + the spill
        # paths in exec/local_planner.py): partial-aggregation mode
        # transitions (full -> shrunken -> bypass and back), recursive
        # spill repartition rounds (salted re-hash of an over-budget
        # partition), heavy-hitter keys split into dedicated bounded
        # paths, and bounded chunked fallbacks at max recursion depth —
        # every strategy switch is a first-class observable event
        self.agg_mode_downgrades = 0
        self.agg_mode_upgrades = 0
        self.agg_recursions = 0
        self.join_recursions = 0
        self.heavy_key_splits = 0
        self.spill_fallbacks = 0
        # memory pressure that ended an attempt of the query: times the
        # node pool's low-memory killer chose it (exec/memory.py), and
        # allocations the device itself refused (XLA RESOURCE_EXHAUSTED,
        # errors.EXCEEDED_DEVICE_MEMORY_LIMIT)
        self.memory_kills = 0
        self.device_oom_errors = 0
        # rows a semi, anti or mark join collected as its build and sent
        # through its probe, and groups the final aggregates emitted (a
        # state of the data, not a score): `count_rows`
        self.semi_join_build_rows = 0
        self.semi_join_probe_rows = 0
        self.aggregate_groups_out = 0
        self._rows_on_device: List[Tuple[str, Any]] = []
        # the executor thread's named host activities (`activity`): self
        # seconds and entries by name; seconds of those that closed
        # inside the one open now
        self.host_s: Dict[str, float] = collections.defaultdict(float)
        self.host_n: Dict[str, int] = collections.defaultdict(int)
        self._closed_s = 0.0
        # compiles as XLA reports them (jit_cache's monitoring listener,
        # on the thread that compiled): programs it compiled for this
        # query and their wall, whatever called for them — an AOT site,
        # a `cached_kernel`'s first call, a retrace of a cached kernel
        # for new avals, an eager jnp op; executables it loaded from the
        # persistent compilation cache instead (a reload, not a
        # compile); and the seconds of tracing and lowering around both
        # (as reported: a trace nested in another counts in both)
        self.backend_compiles = 0
        self.backend_compile_s = 0.0
        self.backend_cache_loads = 0
        self.backend_compiled: List[str] = []
        self.trace_lower_s = 0.0

    # ----------------------------------------------------------- spans

    @contextlib.contextmanager
    def span(self, name: str, kind: str = "internal", **attrs):
        """A span of the query's tree. A phase (`planning`, `execution`,
        `result_fetch`, `mesh_stage`) is also the profiler annotation
        `request__<name>`, on the same thread line as its activities."""
        s = Span(name, kind=kind, attrs=attrs)
        self._stack[-1].children.append(s)
        self._stack.append(s)
        annotation = TraceAnnotation(f"request__{name}") \
            if kind == "phase" and _profiling() else NO_ACTIVITY
        try:
            with annotation:
                yield s
        finally:
            s.finish()
            self._stack.pop()

    def activity(self, name: str, detail: Optional[str] = None
                 ) -> _Activity:
        """`with collector.activity("host_read", "merge_counts"):` — one
        named piece of the executor thread's host time (ACTIVITIES).
        `detail` only from bounded sets: a program name, a site name."""
        return _Activity(self, name, detail)

    @contextlib.contextmanager
    def phase(self, name: str):
        """A named query phase (planning/execution): a span plus an
        accumulated wall bucket — retries re-enter the same bucket."""
        with self.span(name, kind="phase") as s:
            try:
                yield s
            finally:
                s.finish()
                self.phases[name] = self.phases.get(name, 0.0) + s.wall_s

    # ------------------------------------------------------- operators

    def register(self, node) -> OperatorStats:
        """Stats slot for a plan node (the SAME node object re-executed —
        a task retry, a shared subtree, a per-shard task — accumulates
        into one slot; a QUERY-level re-run re-plans, so the runner
        clears `operators` between attempts to keep id() keys valid)."""
        st = self.operators.get(id(node))
        if st is None:
            st = OperatorStats(
                id(node), type(node).__name__,
                source_ids=tuple(id(s) for s in node.sources))
            self.operators[id(node)] = st
        return st

    def input_rows(self, st: OperatorStats) -> int:
        return sum(self.operators[s].output_rows
                   for s in st.source_ids if s in self.operators)

    # -------------------------------------------------------- counters

    def add_output(self, rows: int, nbytes: int) -> None:
        self.output_rows += int(rows)
        self.output_bytes += int(nbytes)

    def add_spill(self, nbytes: int) -> None:
        self.spilled_bytes += int(nbytes)

    def jit_hit(self, key=None) -> None:
        self.jit_hits += 1

    def jit_miss(self, key=None) -> None:
        self.jit_misses += 1

    def jit_param_hit(self, key=None) -> None:
        self.jit_param_hits += 1

    @property
    def fenced(self) -> bool:
        """True when this query measures device time: the jit cache then
        pins and times every kernel dispatch (`jit_cache._timed`)."""
        return self.operator_level or self.fence

    def add_device_time(self, wall_s: float) -> None:
        """One fenced kernel dispatch's measured device wall (a fused
        chain's per-operator shares land on OperatorStats)."""
        self.device_time_s += float(wall_s)

    def add_compile(self, wall_s: float, hlo_ops: int = 0,
                    nbytes: float = 0.0,
                    end_s: Optional[float] = None) -> None:
        """One XLA compile this query triggered (jit-cache AOT site);
        `end_s` is `time.monotonic()` when it ended: the `compile` span."""
        end = time.monotonic() if end_s is None else float(end_s)
        self._stack[-1].children.append(Span(
            "compile", kind="phase", start_s=end - float(wall_s),
            end_s=end, attrs={"hlo_ops": int(hlo_ops)}))
        self.compile_time_s += float(wall_s)
        self.jit_compiles += 1
        self.compiled_hlo_ops += int(hlo_ops)
        self.estimated_bytes += float(nbytes)

    def compile_span(self, start_s: float, end_s: float) -> None:
        """The first call of a `jit_cache.cached_kernel` program, which
        traces and compiles inside `jax.jit` (times on `time.monotonic()`).
        A span and nothing else: `compile_time_ms` and `jit_compiles` stay
        what the AOT sites measured, since this wall holds the trace and
        the dispatch too."""
        self._stack[-1].children.append(Span(
            "compile", kind="phase", start_s=float(start_s),
            end_s=float(end_s), attrs={"first_call": True}))

    def backend_compile(self, fun_name: str, wall_s: float,
                        reloaded: bool, in_compile_span: bool) -> None:
        """XLA's own report of one executable made on this query's
        thread, `wall_s` ago to now: compiled, or `reloaded` from the
        persistent compilation cache. Outside the jit cache's own
        `compile` spans (an AOT compile, a `cached_kernel`'s first call)
        it gets one, so the stall is nobody's dispatch."""
        if reloaded:
            self.backend_cache_loads += 1
        else:
            self.backend_compiles += 1
            self.backend_compile_s += float(wall_s)
            self.backend_compiled.append(fun_name)
            del self.backend_compiled[:-_BACKEND_COMPILED_KEPT]
        if not in_compile_span:
            end = time.monotonic()
            self._stack[-1].children.append(Span(
                "compile", kind="phase", start_s=end - float(wall_s),
                end_s=end, attrs={"backend_compile": fun_name}))
            # and, after the fact, the activity `compile` closed inside
            # whatever is open: a jit call that retraced, an eager op
            self._closed_s += float(wall_s)
            self.host_s["compile"] += float(wall_s)
            self.host_n["compile"] += 1

    def plan_cache_hit(self) -> None:
        self.plan_cache_hits += 1

    def plan_cache_miss(self) -> None:
        self.plan_cache_misses += 1

    def result_cache_hit(self) -> None:
        self.result_cache_hits += 1

    def result_cache_miss(self) -> None:
        self.result_cache_misses += 1

    def scan_cache_hit(self) -> None:
        self.scan_cache_hits += 1

    def scan_cache_miss(self) -> None:
        self.scan_cache_misses += 1

    def table_cache_hit(self) -> None:
        self.table_cache_hits += 1

    def table_cache_miss(self) -> None:
        self.table_cache_misses += 1

    def add_scan_staging(self, nbytes: int, host_bytes: int = 0) -> None:
        """Bytes of the pages a table scan pulled from its connector
        (scan- and table-cache hits add nothing), and how many of them
        the page source moved host -> device to make the page."""
        self.scan_staging_bytes += int(nbytes)
        self.scan_host_staging_bytes += int(host_bytes)

    def count_compaction(self, deferred: bool) -> None:
        if deferred:
            self.compactions_deferred += 1
        else:
            self.compactions_run += 1

    def count_walk(self, pages: int, filters: bool) -> None:
        """One launch of a walking chain (local_planner.compose_walk) over
        `pages` live pages; where the chain filters, each is a deferred
        compaction as it was when it was a launch of its own."""
        self.chain_walks += 1
        self.chain_walk_pages += pages
        if filters:
            self.compactions_deferred += pages

    def count_probe_compaction(self, lanes_in: int, lanes_gathered: int
                               ) -> None:
        """One page of the probe path compacted from `lanes_in` lanes
        through `lanes_gathered` gather indices: 0 of them skipped it,
        all of them is the full form, fewer the tight one."""
        if lanes_gathered == 0:
            self.probe_compactions_skipped += 1
            return
        if lanes_gathered < lanes_in:
            self.probe_compactions_tight += 1
        else:
            self.probe_compactions_full += 1
        self.probe_compaction_lanes_in += int(lanes_in)
        self.probe_compaction_lanes_gathered += int(lanes_gathered)

    def count_probe_lookup(self, table: str) -> None:
        """One join's lookup decision: `row_table`, `position_table`,
        `set_table` or `search`."""
        name = "probe_lookups_" + table
        setattr(self, name, getattr(self, name) + 1)

    def count_rows(self, name: str, num_rows) -> None:
        """Add a page's row count to the counter `name`. A count that is
        still a device scalar is kept as it is and read with the others
        when the snapshot is taken: no sync on the path that counts."""
        if isinstance(num_rows, numbers.Integral):
            setattr(self, name, getattr(self, name) + int(num_rows))
        else:
            self._rows_on_device.append((name, num_rows))

    def _read_rows_on_device(self) -> None:
        if self._rows_on_device:
            from trino_tpu.exec.jit_cache import host_read
            pending, self._rows_on_device = self._rows_on_device, []
            for (name, _), n in zip(pending, host_read(
                    [n for _, n in pending], "rows_on_device", self)):
                setattr(self, name, getattr(self, name) + int(n))

    def count_program_notes(self, notes, chain: bool = True) -> None:
        """One dispatch of a program whose trace noted `notes`
        (page.note_trace). `chain`: a chain or mesh program
        (jit_cache.profiled_kernel), whose direct reduces PR 29 counts; a
        `cached_kernel` program — the local merge steps — counts its
        sorted reduces alone."""
        if chain:
            self.direct_reduces_masked += "direct_reduce_masked" in notes
            self.direct_reduces_scattered += \
                "direct_reduce_scattered" in notes
        lanes = [int(fact.partition(":")[2]) for fact in notes
                 if fact.startswith("sorted_reduce_scan:")]
        self.sorted_reduces_scanned += bool(lanes)
        self.sorted_reduce_lanes += sum(lanes)

    def add_pruned(self, files: int = 0, row_groups: int = 0) -> None:
        self.files_pruned += int(files)
        self.row_groups_pruned += int(row_groups)

    def add_streamed(self, chunks: int, rows: int) -> None:
        self.streamed_chunks += int(chunks)
        self.streamed_rows += int(rows)

    def add_exchange(self, mode: str, rows: int = 0, nbytes: int = 0
                     ) -> None:
        """One inter-fragment exchange applied; mode 'fused' (collective
        inside a co-scheduled mesh program) or 'staged' (standalone
        collective over host-staged fragment outputs)."""
        if mode == "fused":
            self.exchanges_fused += 1
        else:
            self.exchanges_staged += 1
        self.exchange_rows += int(rows)
        self.exchange_bytes += int(nbytes)

    # -------------------------------------------------------- finish

    def finish(self) -> None:
        self.root.finish()

    @property
    def execution_s(self) -> float:
        return self.phases.get("execution", 0.0)

    @property
    def planning_s(self) -> float:
        return self.phases.get("planning", 0.0)

    @property
    def host_time_s(self) -> float:
        """Execution wall with measured device and compile time taken
        out: what the HOST spent scheduling, staging, and shuffling.
        Host time only for a fenced query; unfenced device_time_s is 0,
        this is the execution wall less compiles, and the snapshot
        reports null (cpu_time_ms keeps the number: the wire wants one)."""
        return max(self.execution_s - self.device_time_s
                   - self.compile_time_s, 0.0)

    def request_spans(self) -> List[List[Any]]:
        """[[name, start, end], ...] on time.monotonic() for the spans
        named in REQUEST_SPANS, in tree order (not the operator tree)."""
        out: List[List[Any]] = []

        def walk(span: Span) -> None:
            if span.name in REQUEST_SPANS and span.kind == "phase":
                end = span.end_s if span.end_s is not None \
                    else time.monotonic()
                out.append([span.name, round(span.start_s, 6),
                            round(end, 6)])
            for child in span.children:
                walk(child)
        walk(self.root)
        return out

    def operator_rows(self) -> List[Dict[str, Any]]:
        out = []
        for st in self.operators.values():
            out.append({
                "name": st.name,
                "input_rows": self.input_rows(st),
                "output_rows": st.output_rows,
                "output_bytes": st.output_bytes,
                "pages": st.pages,
                "wall_ms": round(st.wall_s * 1000, 3),
                "device_ms": round(st.device_s * 1000, 3),
            })
        return out

    def snapshot(self) -> Dict[str, Any]:
        """The immutable query-end rollup (QueryStats.java wire shape):
        what QueryInfo.stats and event payloads carry, and what
        benchmark/ reads."""
        spans = self.request_spans()
        self._read_rows_on_device()
        snap: Dict[str, Any] = {
            "query_id": self.query_id,
            "wall_s": round(self.root.wall_s, 6),
            "planning_s": round(self.planning_s, 6),
            "execution_s": round(self.execution_s, 6),
            "output_rows": self.output_rows,
            "output_bytes": self.output_bytes,
            "spilled_bytes": self.spilled_bytes,
            "jit_hits": self.jit_hits,
            "jit_misses": self.jit_misses,
            "jit_param_hits": self.jit_param_hits,
            "queued_ms": round(sum(
                e - s for n, s, e in spans if n == "queued") * 1000, 3),
            "spans": spans,
            "device_time_ms": round(self.device_time_s * 1000, 3)
            if self.fenced else None,
            "compile_time_ms": round(self.compile_time_s * 1000, 3),
            "host_time_ms": round(self.host_time_s * 1000, 3)
            if self.fenced else None,
            "jit_compiles": self.jit_compiles,
            "compiled_hlo_ops": self.compiled_hlo_ops,
            "estimated_bytes": self.estimated_bytes,
            "backend_compiles": self.backend_compiles,
            "backend_compile_ms": round(self.backend_compile_s * 1000, 3),
            "backend_cache_loads": self.backend_cache_loads,
            "backend_compiled": list(self.backend_compiled),
            "trace_lower_ms": round(self.trace_lower_s * 1000, 3),
            "host_ms": {name: round(s * 1000, 3)
                        for name, s in self.host_s.items()},
            "host_calls": dict(self.host_n),
            "kernel_calls": self.host_n.get("kernel_call", 0),
            "host_reads": self.host_n.get("host_read", 0),
            "page_pulls": self.host_n.get("page_pull", 0),
            "plan_cache_hits": self.plan_cache_hits,
            "plan_cache_misses": self.plan_cache_misses,
            "result_cache_hits": self.result_cache_hits,
            "result_cache_misses": self.result_cache_misses,
            "scan_cache_hits": self.scan_cache_hits,
            "scan_cache_misses": self.scan_cache_misses,
            "table_cache_hits": self.table_cache_hits,
            "table_cache_misses": self.table_cache_misses,
            "scan_staging_bytes": self.scan_staging_bytes,
            "scan_host_staging_bytes": self.scan_host_staging_bytes,
            "compactions_deferred": self.compactions_deferred,
            "compactions_run": self.compactions_run,
            "chain_walks": self.chain_walks,
            "chain_walk_pages": self.chain_walk_pages,
            "probe_compactions_tight": self.probe_compactions_tight,
            "probe_compactions_full": self.probe_compactions_full,
            "probe_compactions_skipped": self.probe_compactions_skipped,
            "probe_compaction_lanes_in": self.probe_compaction_lanes_in,
            "probe_compaction_lanes_gathered":
                self.probe_compaction_lanes_gathered,
            "probe_lookups_row_table": self.probe_lookups_row_table,
            "probe_lookups_position_table":
                self.probe_lookups_position_table,
            "probe_lookups_set_table": self.probe_lookups_set_table,
            "probe_lookups_search": self.probe_lookups_search,
            "semi_build_lanes_set": self.semi_build_lanes_set,
            "semi_build_lanes_sorted": self.semi_build_lanes_sorted,
            "probe_lookup_lanes": self.probe_lookup_lanes,
            "probe_lookup_lanes_search": self.probe_lookup_lanes_search,
            "like_tables_built": self.like_tables_built,
            "probe_overflow_reruns": self.probe_overflow_reruns,
            "cross_joins": self.cross_joins,
            "semi_join_build_rows": self.semi_join_build_rows,
            "semi_join_probe_rows": self.semi_join_probe_rows,
            "aggregate_groups_out": self.aggregate_groups_out,
            "direct_reduces_masked": self.direct_reduces_masked,
            "direct_reduces_scattered": self.direct_reduces_scattered,
            "sorted_reduces_scanned": self.sorted_reduces_scanned,
            "sorted_reduce_lanes": self.sorted_reduce_lanes,
            "group_by_lanes_in_order": self.group_by_lanes_in_order,
            "group_by_lanes_sorted": self.group_by_lanes_sorted,
            "files_pruned": self.files_pruned,
            "row_groups_pruned": self.row_groups_pruned,
            "streamed_chunks": self.streamed_chunks,
            "streamed_rows": self.streamed_rows,
            "retries": self.retries,
            "faults_injected": self.faults_injected,
            "exchanges_fused": self.exchanges_fused,
            "exchanges_staged": self.exchanges_staged,
            "exchange_rows": self.exchange_rows,
            "exchange_bytes": self.exchange_bytes,
            "mesh_devices": self.mesh_devices,
            "mesh_programs": self.mesh_programs,
            "mesh_program_rounds": self.mesh_program_rounds,
            "mesh_scan_moved_bytes": self.mesh_scan_moved_bytes,
            "mesh_params": self.mesh_params,
            "slices_executed": self.slices_executed,
            "checkpoints_saved": self.checkpoints_saved,
            "checkpoints_restored": self.checkpoints_restored,
            "checkpoint_bytes": self.checkpoint_bytes,
            "preempt_latency_ms": self.preempt_latency_ms,
            "agg_mode_downgrades": self.agg_mode_downgrades,
            "agg_mode_upgrades": self.agg_mode_upgrades,
            "agg_recursions": self.agg_recursions,
            "join_recursions": self.join_recursions,
            "heavy_key_splits": self.heavy_key_splits,
            "spill_fallbacks": self.spill_fallbacks,
            "memory_kills": self.memory_kills,
            "device_oom_errors": self.device_oom_errors,
        }
        if self.operators:
            snap["operators"] = self.operator_rows()
        return snap

    def trace_json(self) -> Dict[str, Any]:
        """The per-query structured span dump (query -> phases ->
        fragments/exchanges), with operator spans synthesized from the
        collected OperatorStats when operator-level collection ran (a
        streaming operator has no contiguous lifetime, so its 'span' is
        its inclusive wall, parented under the query root)."""
        dump = self.root.to_json()
        if self.operators:
            origin = self.root.start_s
            ops = []
            for st in self.operators.values():
                op = Span(st.name, kind="operator", start_s=origin,
                          attrs={"output_rows": st.output_rows,
                                 "output_bytes": st.output_bytes,
                                 "pages": st.pages,
                                 "device_ms": round(st.device_s * 1000,
                                                    3)})
                op.end_s = origin + st.wall_s
                ops.append(op._to_json(origin))
            dump.setdefault("children", []).extend(ops)
        return dump


def maybe_span(collector: Optional[QueryStatsCollector], name: str,
               kind: str = "internal", **attrs):
    """Span scope that degrades to a no-op without a collector (the
    execution paths run with collector=None outside runner.execute)."""
    if collector is None:
        return contextlib.nullcontext()
    return collector.span(name, kind=kind, **attrs)


def maybe_phase(collector: Optional[QueryStatsCollector], name: str):
    if collector is None:
        return contextlib.nullcontext()
    return collector.phase(name)


def maybe_activity(collector: Optional[QueryStatsCollector], name: str,
                   detail: Optional[str] = None):
    """`collector.activity(...)`, or a no-op without a collector."""
    if collector is None:
        return NO_ACTIVITY
    return _Activity(collector, name, detail)


def render_analyzed_plan(plan, collector: QueryStatsCollector,
                         total_rows: int, total_wall_s: float,
                         label: str = "single device") -> str:
    """EXPLAIN ANALYZE text: the executed plan annotated with each node's
    rows, bytes, and wall time (PlanPrinter.textDistributedPlan with
    operator stats). Exclusive time subtracts the children's inclusive
    walls, clamped at zero (a fused child can complete inside its
    parent's read)."""
    from trino_tpu.planner.nodes import format_plan

    def cumulative(st) -> float:
        """Inclusive wall estimate: fused slots hold an EXCLUSIVE
        cost-model share, so their subtree adds the children's
        cumulative walls; wrapper-measured slots are already
        inclusive."""
        if not st.fused:
            return st.wall_s
        return st.wall_s + sum(
            cumulative(collector.operators[s]) for s in st.source_ids
            if s in collector.operators)

    def annotate(node):
        st = collector.operators.get(id(node))
        if st is None:
            return ""
        if st.fused:
            # the share IS this operator's own time (exclusive by
            # construction — subtracting inclusive children from it
            # would clamp every fused operator to 0.00ms)
            own = st.wall_s
        else:
            child_wall = sum(collector.operators[s].wall_s
                             for s in st.source_ids
                             if s in collector.operators)
            own = max(st.wall_s - child_wall, 0.0)
        text = (f"output: {st.output_rows} rows ({st.pages} pages, "
                f"{_fmt_bytes(st.output_bytes)}), "
                f"time: {own * 1000:.2f}ms "
                f"({cumulative(st) * 1000:.2f}ms cumulative)")
        if st.device_s > 0:
            text += f", device: {st.device_s * 1000:.2f}ms"
        return text

    text = format_plan(plan, annotate=annotate)
    text += (f"\n\nQuery: {total_rows} rows, "
             f"wall {total_wall_s * 1000:.2f}ms ({label}), "
             f"planning {collector.planning_s * 1000:.2f}ms, "
             f"device {collector.device_time_s * 1000:.2f}ms / "
             f"compile {collector.compile_time_s * 1000:.2f}ms / "
             f"host {collector.host_time_s * 1000:.2f}ms, "
             f"jit {collector.jit_hits} hits / "
             f"{collector.jit_misses} misses / "
             f"{collector.jit_param_hits} param hits / "
             f"{collector.jit_compiles} compiles, "
             f"plan cache {collector.plan_cache_hits} hits / "
             f"{collector.plan_cache_misses} misses")
    if collector.spilled_bytes:
        text += f", spilled {_fmt_bytes(collector.spilled_bytes)}"
    calls, named_s = collector.host_n, collector.host_s
    text += (f"\nhost: {calls.get('kernel_call', 0)} calls / "
             f"{calls.get('host_read', 0)} reads / "
             f"{calls.get('page_pull', 0)} pulls, "
             + " / ".join(f"{name} {named_s[name] * 1000:.2f}ms"
                          for name in ACTIVITIES if name in named_s)
             + f", {collector.backend_compiles} backend compiles")
    if (collector.agg_mode_downgrades or collector.agg_mode_upgrades
            or collector.agg_recursions or collector.join_recursions
            or collector.heavy_key_splits or collector.spill_fallbacks):
        text += (f"\nadaptive: {collector.agg_mode_downgrades} agg "
                 f"downgrades / {collector.agg_mode_upgrades} upgrades, "
                 f"{collector.agg_recursions} agg + "
                 f"{collector.join_recursions} join spill recursions, "
                 f"{collector.heavy_key_splits} heavy-key splits, "
                 f"{collector.spill_fallbacks} chunked fallbacks")
    return text


def _fmt_bytes(n: int) -> str:
    from trino_tpu.exec.memory import _fmt_bytes as fmt
    return fmt(int(n))
