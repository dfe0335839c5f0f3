"""Distributed execution: fragmented plans over a device mesh.

Reference parity: execution/scheduler/SqlQueryScheduler.java:112 (stages from
fragments, dependency-ordered start), PlanFragmenter.java:108 (the fragment
tree consumed here), execution/scheduler/PhasedExecutionSchedule.java
(build-before-probe ordering), server/remotetask + execution/buffer (the
HTTP data plane, replaced wholesale by mesh collectives).

TPU-first design (SURVEY §2.11, §7): a single-controller process drives a
`QueryMesh`; each PlanFragment executes as N per-shard "tasks" through the
same operator pipelines as local execution, with leaf scans sharded by split
(`SourcePartitionedScheduler` analog) and REMOTE exchanges lowered to ONE
jitted `shard_map` collective program per fragment edge:

  REPARTITION -> all_to_all_by_key (FIXED_HASH_DISTRIBUTION)
  BROADCAST   -> broadcast_page    (FIXED_BROADCAST_DISTRIBUTION)
  GATHER      -> broadcast_page, shard 0 consumes (SINGLE distribution)
  MERGE       -> gather + re-sort  (ordered MergeOperator analog)

Pages cross fragment boundaries without leaving devices: per-shard outputs
are stacked into one globally-sharded Page (leading axis = workers), the
collective runs on the mesh, and the result is viewed back per-shard through
the sharded array's addressable shards. The all_to_all bucket capacity uses
the same overflow-ladder contract as the join/page kernels: the collective
psums an overflow count and the host re-runs the exchange with a doubled
bucket until it fits.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.exec.local_planner import (
    ExecutionError, LocalExecutionPlanner, PageStream, _layout, _next_pow2,
    compose_chain)
from trino_tpu.exec.jit_cache import (cached_kernel, host_read,
                                      observed_activity, pulled)
from trino_tpu.exec.runner import LocalQueryRunner, MaterializedResult
from trino_tpu.metadata import Metadata, Session
from trino_tpu.ops import AggSpec, SortKey, Step, hash_aggregate, order_by
from trino_tpu.ops.aggregate import get_aggregate
from trino_tpu.page import (Column, Page, count_host_staging,
                            union_dictionaries)
from trino_tpu.parallel.exchange import (all_to_all_by_key, broadcast_page)
from trino_tpu.parallel.mesh import QueryMesh
from trino_tpu.planner.nodes import (
    AggregationNode, AggStep, ExchangeKind, OutputNode, Symbol,
    TableScanNode, ValuesNode)
from trino_tpu.planner.optimizer import (
    PlanFragment, RemoteSourceNode, fragment_plan, optimize)
from trino_tpu.sql import tree as t


class ShardExecutionPlanner(LocalExecutionPlanner):
    """One distributed 'task': the local operator pipelines, executing shard
    `shard` of `n_shards` (execution/SqlTaskExecution.java analog).

    Differences from local execution:
      - leaf scans read only this shard's splits (split.part % n == shard);
      - RemoteSourceNodes read the post-collective input staged for this
        shard by the DistributedQueryRunner;
      - VALUES (SINGLE-distribution leaves) materialize on shard 0 only;
      - PARTIAL/FINAL aggregation steps execute as written instead of being
        fused into one operator (the exchange sits between them);
      - unique ids are disjoint across shards.
    """

    def __init__(self, metadata: Metadata, session: Session, shard: int,
                 n_shards: int,
                 exchange_inputs: Dict[int, List[Optional[Page]]],
                 device=None):
        super().__init__(metadata, session)
        self.shard = shard
        self.n_shards = n_shards
        self.exchange_inputs = exchange_inputs
        self.mem_device = shard   # per-chip reservation attribution
        # the mesh device this task's pipelines run on: leaf pages are
        # placed here, and every downstream kernel follows its inputs, so
        # per-shard work queues on per-device streams and OVERLAPS across
        # the mesh (NodeScheduler split->node assignment analog)
        self.device = device

    # ------------------------------------------------------------- leaves

    def _exec_TableScanNode(self, node: TableScanNode) -> PageStream:
        conn = self.metadata.connector(node.catalog)
        columns = [c for _, c in node.assignments]
        symbols = tuple(s for s, _ in node.assignments)
        col = self.collector
        # device-resident table cache: this shard's row range slices out
        # of the resident columns — a cross-device placement is a
        # device-to-device copy, never host->device staging (the counter
        # contract the table cache exists for). Hit/miss counts on
        # shard 0 only, so a fragment's scan counts once per scan.
        tcache = None if node.catalog == "system" else self.table_cache
        if tcache is not None:
            st = node.table.name
            tkey = (node.catalog, st.schema, st.table)
            names = [c.name for c in columns]
            # ONE resolution per fragment attempt (the memo is shared by
            # every shard executor of the attempt): a promotion or
            # invalidation landing between shard dispatches must not mix
            # row-range cache shards with split-based connector shards
            # within a single scan
            memo = self.table_cache_memo
            memo_key = (tkey, tuple(names))
            if memo is not None and memo_key in memo:
                entry = memo[memo_key]
            else:
                # the mesh's resident shards first (block i is on chip i
                # already), then a full-length entry to slice
                entry = None if node.table.limit is not None else \
                    tcache.lookup_sharded(tkey, names, self.n_shards,
                                          count=self.shard == 0)
                if entry is None:
                    entry = tcache.lookup(tkey, names,
                                          count=self.shard == 0)
                if memo is not None:
                    memo[memo_key] = entry
            if entry is not None:
                if col is not None and self.shard == 0:
                    col.table_cache_hit()
                from trino_tpu.exec.table_cache import (ShardedTable,
                                                        build_shard_page)
                if isinstance(entry, ShardedTable):
                    my_page = entry.shard_page(names, self.shard)
                else:
                    with observed_activity("eager_slice", "table_cache"):
                        my_page = build_shard_page(entry, names, self.shard,
                                                   self.n_shards)

                def gen_resident(page=my_page):
                    if page is None:
                        return
                    if self.device is not None:
                        page = jax.device_put(page, self.device)
                    self._checkpoint()
                    yield page
                return PageStream(self._sliced(gen_resident()), symbols)
            if col is not None and self.shard == 0:
                col.table_cache_miss()
        handle, _dyn = self._effective_handle(conn, node)
        splits = conn.split_manager.get_splits(
            handle, target_splits=self.n_shards)
        mine = [s for s in splits if s.part % self.n_shards == self.shard]
        cap = self._split_capacity(conn, node, splits)
        # dispatch-loop promotion (round 15, the PR 11 leftover): the
        # dispatch loop used to SERVE table-cache hits but never feed
        # the tier — scan frequency now counts here too (shard 0, once
        # per fragment attempt), and when the working set clears
        # admission the attempt's shard executors pool their staged
        # pages in the shared memo; the LAST shard to finish promotes
        # the full row set, so repeated dispatch-loop scans reach zero
        # host->device staging just like the local loop and mesh paths.
        stage_key = None
        if tcache is not None and self.table_cache_memo is not None \
                and not _dyn and node.table.limit is None \
                and (not getattr(conn.metadata, "supports_zone_maps",
                                 False)
                     or handle.constraint.is_all()):
            dkey = ("promote", tkey, tuple(names))
            if self.shard == 0 and dkey not in self.table_cache_memo:
                count = tcache.note_scan(tkey, names)
                ok = count >= max(int(self.table_cache_min_scans), 1) \
                    and tcache.should_promote(tkey, names)
                self.table_cache_memo[dkey] = (ok, tcache.generation())
            decision = self.table_cache_memo.get(dkey)
            if decision is not None and decision[0]:
                stage_key = ("stage", tkey, tuple(names))

        def gen():
            from trino_tpu.exec.memory import page_bytes
            staged = [] if stage_key is not None else None
            try:
                for split in mine:
                    self._fault_site("scan",
                                     f"{node.table} part {split.part}")
                    for page, moved in count_host_staging(pulled(
                            conn.page_source.pages(split, columns, cap),
                            "connector")):
                        self._checkpoint()
                        if col is not None:
                            col.add_scan_staging(page_bytes(page), moved)
                        if self.device is not None:
                            page = jax.device_put(page, self.device)
                        if staged == [] and not tcache.admits(
                                page, max(self._table_rows(conn, node), 1)):
                            staged = None   # refused by shapes: gather none
                        if staged is not None:
                            staged.append(page)
                        yield page
            finally:
                # shard executors dispatch sequentially on one thread;
                # every shard's get_splits sees the same pruning, so
                # fold shard 0's counters and drop the duplicates
                if self.shard == 0:
                    self._drain_scan_stats(conn)
                else:
                    take = getattr(conn, "take_scan_stats", None)
                    if take is not None:
                        take()
            if staged is not None:
                self._stage_for_promotion(stage_key, staged, node)
        return PageStream(self._sliced(gen()), symbols)

    def _stage_for_promotion(self, stage_key, staged, node) -> None:
        """Pool this shard's fully-scanned pages in the fragment
        attempt's shared memo; the shard that completes the set
        promotes the whole table into the device cache (partial
        consumption — a LIMIT upstream — simply never completes the
        set, which is the conservative outcome)."""
        memo = self.table_cache_memo
        entry = memo.setdefault(("pages",) + stage_key[1:], {})
        entry[self.shard] = staged
        if len(entry) < self.n_shards:
            return
        _, tkey, _names = stage_key
        decision = memo.get(("promote",) + stage_key[1:])
        pages = [p for s in range(self.n_shards) for p in entry[s]]
        if not pages:
            return
        # resident columns live on the default device (the table
        # cache's placement on the CPU mesh); colocate before the
        # promotion's device concat
        dev = jax.devices()[0]
        pages = [jax.device_put(p, dev) for p in pages]
        counts = [int(c) for c in host_read(
            [p.num_rows for p in pages], "promote_counts")]
        # the promoting shard is the LAST one drained (never shard 0);
        # the collector is shared across the attempt's shard executors
        self.table_cache.promote_from_pages(
            tkey, [(c.name, c) for _, c in node.assignments], pages,
            counts, collector=self.collector,
            gen=None if decision is None else decision[1])

    def _split_capacity(self, conn, node: TableScanNode, splits) -> int:
        cap = split_scan_capacity(self.session, conn, node.table, splits)
        if self.slices is not None:
            # same bound as the local scan: one page <= one slice
            cap = min(cap, self.slices.capacity_cap(self.page_capacity))
        return cap

    def _exec_ValuesNode(self, node: ValuesNode) -> PageStream:
        if self.shard != 0:
            return PageStream(iter(()), node.symbols)
        return super()._exec_ValuesNode(node)

    def _exec_RemoteSourceNode(self, node: RemoteSourceNode) -> PageStream:
        pages = self.exchange_inputs.get(node.fragment_id)
        page = None if pages is None else pages[self.shard]
        if page is None:
            return PageStream(iter(()), node.symbols)
        return PageStream(iter([page]), node.symbols)

    # -------------------------------------------------------- aggregation

    def _exec_AggregationNode(self, node: AggregationNode) -> PageStream:
        if node.step == AggStep.SINGLE:
            return super()._exec_AggregationNode(node)
        if node.step == AggStep.PARTIAL:
            return self._exec_partial_agg(node)
        return self._exec_final_agg(node)

    def _agg_specs(self, node: AggregationNode, lay, typ) -> List[AggSpec]:
        specs = []
        for out_sym, call in node.aggregations:
            if call.args:
                arg = call.args[0]
                input_ch: Optional[int] = lay[arg.name] if lay else None
                in_type: Optional[T.Type] = call.input_type
            else:
                input_ch, in_type = None, None
            in2_ch = in2_type = None
            if len(call.args) > 1 and lay:
                arg2 = call.args[1]
                in2_ch, in2_type = lay[arg2.name], arg2.type
            mask_ch = None
            if call.filter is not None:
                mask_ch = lay[call.filter.name]
            specs.append(AggSpec(call.name, input_ch, in_type, mask_ch,
                                 call.distinct, in2_ch, in2_type))
        return specs

    def _exec_partial_agg(self, node: AggregationNode) -> PageStream:
        src = self.execute(node.source)
        lay, typ = _layout(src.symbols)
        key_channels = tuple(lay[s.name] for s in node.group_by)
        specs = tuple(self._agg_specs(node, lay, typ))
        partial_op = compose_chain(
            src.pending, ("agg-partial", key_channels, specs),
            lambda: hash_aggregate(list(key_channels), list(specs),
                                   Step.PARTIAL),
            tail_slot=self._slot(node))

        def gen():
            for page in src.pages:
                yield partial_op(page)
        return PageStream(gen(), node.outputs)

    def _exec_final_agg(self, node: AggregationNode) -> PageStream:
        src = self.execute(node.source)
        specs = tuple(self._agg_specs(node, None, None))
        nkeys = len(node.group_by)
        state_channels = []
        ch = nkeys
        for spec in specs:
            fn = get_aggregate(spec.name, spec.input_type)
            k = len(fn.state(spec.input_type))
            state_channels.append(list(range(ch, ch + k)))
            ch += k
        final_op = cached_kernel(
            ("agg-final", nkeys, specs),
            lambda: hash_aggregate(list(range(nkeys)), list(specs),
                                   Step.FINAL, state_channels))

        def gen():
            page = self._collect(src)
            if page is None \
                    or int(host_read(page.num_rows, "agg_input_rows")) == 0:
                if not node.group_by:
                    yield self._empty_global_agg(node, specs)
                return
            yield final_op(page)
        return PageStream(gen(), node.outputs)

    # ------------------------------------------------------------- unique

    def _exec_AssignUniqueIdNode(self, node) -> PageStream:
        stream = super()._exec_AssignUniqueIdNode(node)
        base = jnp.int64(self.shard) << jnp.int64(44)
        if self.shard == 0:
            return stream

        def gen():
            for page in stream.iter_pages():
                col = page.columns[-1]
                shifted = Column(col.values + base, col.valid, col.type,
                                 None)
                yield Page(page.columns[:-1] + (shifted,), page.num_rows)
        return PageStream(gen(), stream.symbols)


class DistributedQueryRunner(LocalQueryRunner):
    """Multi-shard engine over a QueryMesh.

    Reference parity: testing/DistributedQueryRunner.java:72 — the same SQL
    surface as LocalQueryRunner, but SELECT queries plan with
    `distributed=True`, fragment at REMOTE exchanges, and execute stage-by-
    stage over the mesh with collective exchanges. DDL/DML and session
    statements run through the local path (coordinator-only work).
    """

    def __init__(self, session: Optional[Session] = None,
                 devices: Optional[Sequence] = None):
        super().__init__(session)
        self.mesh = QueryMesh(devices)
        self._exchange_jits: Dict[tuple, object] = {}
        # size the node pool from the backend's measured per-device
        # memory (TPU HBM minus scan-cache budget); no-op on CPU, which
        # keeps the static default (exec/memory.autosize_node_pool)
        from trino_tpu.exec.memory import autosize_node_pool
        autosize_node_pool()

    @classmethod
    def tpch(cls, schema: str = "tiny",
             devices: Optional[Sequence] = None) -> "DistributedQueryRunner":
        from trino_tpu.connector import (blackhole, memory, system, tpcds,
                                         tpch as tpch_conn)
        runner = cls(Session(catalog="tpch", schema=schema), devices)
        runner.catalogs.register("tpch", tpch_conn.create_connector())
        runner.catalogs.register("tpcds", tpcds.create_connector())
        runner.catalogs.register("memory", memory.create_connector())
        runner.catalogs.register("blackhole", blackhole.create_connector())
        from trino_tpu.connector import lake
        runner.catalogs.register("lake", lake.create_connector())
        runner.catalogs.register("system", system.create_connector())
        return runner

    # ------------------------------------------------------------ execute

    def _execute_query(self, query: t.Query) -> MaterializedResult:
        plan = self._plan_query(query)   # through the plan cache
        from trino_tpu.exec.plan_cache import plan_tables
        self._last_plan_tables = plan_tables(plan)  # result-cache keys
        if self._collector is not None:
            self._collector.mesh_devices = self.mesh.n
        with self._phase("execution"):
            frag = fragment_plan(plan)
            # children schedule (and retry) independently BEFORE the
            # root's retry scope opens: a root attempt failure re-runs
            # only the root fragment against the already-materialized
            # exchange inputs
            exchange_inputs = self._schedule_children(frag)
            with self._frag_span(frag, "fragment-root"):
                return self._retry_task(
                    "fragment-root",
                    lambda: self._root_attempt(frag, plan, exchange_inputs))

    def _frag_span(self, frag: PlanFragment, name: str):
        """A fragment trace span covering the fragment's retry scope
        (query -> fragment in the span tree); no-op without a collector."""
        from trino_tpu.obs.stats import maybe_span
        return maybe_span(self._collector, name, kind="fragment",
                          partitioning=frag.partitioning)

    def _root_attempt(self, frag: PlanFragment, plan: OutputNode,
                      exchange_inputs) -> MaterializedResult:
        self._check_deadline()
        executor = ShardExecutionPlanner(
            self.metadata, self.session, 0, self.mesh.n, exchange_inputs)
        executor.faults = self._faults
        executor.deadline = self._deadline
        executor.collector = self._collector
        executor.exec_params = self._exec_params
        executor.slices = self._slices
        executor.adaptive = getattr(self, "_adaptive", None)
        executor.table_cache = self._active_table_cache()
        executor.table_cache_min_scans = int(
            self.session.get("table_cache_min_scans"))
        if self._memory is not None:
            executor.memory = self._memory   # query-level shared ledger
        root_stream = executor.execute(frag.root)
        types = [s.type for s in plan.symbols]
        rows = []
        nbytes = 0
        from trino_tpu.exec.memory import live_page_bytes
        for page in root_stream.iter_pages():
            self._check_deadline()      # page-batch cancellation point
            n = int(host_read(page.num_rows, "result_rows"))
            if n == 0:
                continue
            nbytes += live_page_bytes(page, n)
            with observed_activity("to_host"):
                cols = page.to_host(n)
            from trino_tpu.exec.runner import _to_python
            with observed_activity("rows_to_python"):
                for i in range(n):
                    rows.append(tuple(_to_python(cols[j][i], types[j])
                                      for j in range(len(cols))))
        if self._faults is not None:
            self._faults.site("fragment", "root")
        self._last_output_nbytes = nbytes
        if self._collector is not None:
            self._collector.add_output(len(rows), nbytes)
        return MaterializedResult(list(plan.column_names), types, rows)

    def _plan_query_for_analyze(self, query: t.Statement) -> OutputNode:
        """EXPLAIN ANALYZE executes with the LOCAL executor, but this
        runner's shared cache holds distributed (exchange-bearing) plans
        — plan outside the cache so neither path poisons the other."""
        return self._plan(query)

    def _plan_for_execution(self, query: t.Statement) -> OutputNode:
        """Distributed planning primitive behind the base runner's
        `_plan_query` cache: a repeated shape (or an EXECUTE re-run)
        reuses the fragmented-and-optimized plan too."""
        from trino_tpu.planner import LogicalPlanner
        with self._phase("planning"):
            plan = LogicalPlanner(self.metadata, self.session).plan(query)
            return optimize(plan, self.metadata, self.session,
                            distributed=True)

    # --------------------------------------------------------- scheduling

    def _schedule_children(self, frag: PlanFragment
                           ) -> Dict[int, List[Optional[Page]]]:
        """Run every child fragment and lower its consuming exchange to a
        collective. Build-before-probe: later sources (join build sides are
        the right/second child) schedule first (PhasedExecutionSchedule).

        Eligible child chains co-schedule first (exec/mesh_exec.py): the
        whole fragment subtree + its exchange runs as ONE shard_map
        program and pages never stage through the host. Unsupported
        shapes fall back to the per-shard dispatch loop below (which
        recursively offers mesh co-scheduling to ITS children)."""
        exchange_inputs: Dict[int, List[Optional[Page]]] = {}
        for child in reversed(frag.children):
            remote = _find_remote(frag.root, child.fragment_id)
            mesh_pages = self._try_mesh_child(child, remote)
            if mesh_pages is not None:
                exchange_inputs[child.fragment_id] = mesh_pages
                continue
            child_pages = self._run_fragment_to_pages(child)
            # the exchange apply is its own retry scope: a transient
            # collective failure (or injected fault) re-applies the
            # idempotent collective against the child's buffered output —
            # the task-output-buffer re-fetch of the reference's retry
            with self._exchange_span(child, remote):
                exchange_inputs[child.fragment_id] = self._retry_task(
                    f"exchange-{child.fragment_id}",
                    lambda p=child_pages, r=remote:
                        self._apply_exchange(p, r))
        return exchange_inputs

    def _try_mesh_child(self, child: PlanFragment, remote
                        ) -> Optional[List[Optional[Page]]]:
        """Co-scheduled mesh execution of one child fragment chain, or
        None to use the dispatch-loop fallback. Disabled under fault
        injection (chaos must see per-shard sites). Operator-level stats
        runs STAY on the mesh (round 13): the program emits
        program-level operator rows with cost-apportioned device walls
        (mesh_exec._record_program_stats) instead of falling back to the
        per-shard dispatch loop — turning stats on no longer changes the
        data plane (exchanges stay fused)."""
        if not bool(self.session.get("mesh_execution")):
            return None
        if self.mesh.n < 2:
            return None
        if self._faults is not None:
            return None
        from trino_tpu.exec import mesh_exec
        try:
            with self._frag_span(child,
                                 f"mesh-fragment-{child.fragment_id}"):
                pages = mesh_exec.run_co_scheduled(self, child, remote)
                # the consuming exchange ran INSIDE the program; record
                # its span (zero own-wall: its time is the fragment's)
                with self._exchange_span(child, remote, "fused"):
                    pass
                return pages
        except (mesh_exec.MeshUnsupported, NotImplementedError):
            return None

    def _exchange_span(self, child: PlanFragment, remote,
                       data_plane: str = "staged"):
        from trino_tpu.obs.stats import maybe_span
        return maybe_span(
            self._collector, f"exchange-{child.fragment_id}",
            kind="exchange",
            exchange_kind=str(remote.kind).rsplit(".", 1)[-1],
            data_plane=data_plane)

    def _run_fragment_to_pages(self, frag: PlanFragment
                               ) -> List[Optional[Page]]:
        """Run one non-root fragment on its participating shards; returns one
        concatenated output Page per shard (None = shard produced nothing).
        The per-shard execution is one retry scope (RetryPolicy.TASK's
        unit): retryable failures re-run THIS fragment only — its children
        have already completed their own scopes."""
        exchange_inputs = self._schedule_children(frag)
        with self._frag_span(frag, f"fragment-{frag.fragment_id}"):
            return self._retry_task(
                f"fragment-{frag.fragment_id}",
                lambda: self._fragment_attempt(frag, exchange_inputs))

    def _fragment_attempt(self, frag: PlanFragment, exchange_inputs
                          ) -> List[Optional[Page]]:
        from trino_tpu.exec.sliced.checkpoint import OperatorCheckpoint
        from trino_tpu.obs.stats import maybe_span
        self._check_deadline()
        shards = [0] if frag.partitioning == "single" else \
            list(range(self.mesh.n))
        # per-shard checkpoints (exec/sliced/checkpoint.py): a fragment
        # retry resumes from the shards that already completed instead
        # of re-running the whole fragment — each attempt checkpoints
        # every shard it finishes (raw page list at dispatch, merged
        # output at merge), so progress across attempts is monotonic:
        # slices re-executed < slices total, and an attempt that finds
        # every shard checkpointed executes nothing at all.
        store = getattr(self, "_ckpts", None)

        def scope_of(shard: int) -> str:
            return f"fragment-{frag.fragment_id}/shard-{shard}"

        # one table-cache resolution per (table, columns) for the WHOLE
        # attempt: shard executors share this memo so a concurrent
        # promotion/invalidation can never split one scan across the
        # cache and connector data planes
        tcache_memo: Dict[tuple, object] = {}

        # dispatch every non-checkpointed shard's pipeline before the
        # batched result sync. Leaf pages are device_put onto mesh device
        # `shard`, so each task's kernels queue on ITS device's stream:
        # STREAMING fragments (scan/filter/partial-agg) overlap across
        # the mesh, while a fragment with a blocking operator still
        # serializes at that operator's internal count fetch — full
        # overlap needs the per-fragment shard_map program.
        # Reference: SqlQueryScheduler.java:538 concurrent stage tasks.
        restored: List[Tuple[int, ShardExecutionPlanner, object]] = []
        dispatched: List[Tuple[int, ShardExecutionPlanner, list]] = []
        for shard in shards:
            self._check_deadline()
            executor = ShardExecutionPlanner(
                self.metadata, self.session, shard, self.mesh.n,
                exchange_inputs, device=self.mesh.device_of(shard))
            executor.faults = self._faults
            executor.deadline = self._deadline
            executor.collector = self._collector
            executor.exec_params = self._exec_params
            executor.slices = self._slices
            executor.adaptive = getattr(self, "_adaptive", None)
            executor.table_cache = self._active_table_cache()
            executor.table_cache_min_scans = int(
                self.session.get("table_cache_min_scans"))
            executor.table_cache_memo = tcache_memo
            if self._memory is not None:
                executor.memory = self._memory  # shards share the ledger
            ck = store.load(scope_of(shard)) if store is not None else None
            if ck is not None:
                # durable state from a previous attempt: skip execution
                # (complete -> reuse the merged output; raw -> merge the
                # already-produced pages below, without re-running)
                with maybe_span(self._collector, "checkpoint-restore",
                                kind="checkpoint", scope=scope_of(shard),
                                complete=ck.complete):
                    restored.append((shard, executor, ck))
                continue
            pages = list(executor.execute(frag.root).iter_pages())
            dispatched.append((shard, executor, pages))
            if store is not None:
                # transient staging (count=False): replaced by the
                # merged output below — the saved/bytes counters track
                # durable per-shard state once, not this intermediate
                store.save(scope_of(shard), OperatorCheckpoint(
                    scope=scope_of(shard), cursor=len(pages),
                    pages=list(pages)), count=False)
        out: List[Optional[Page]] = [None] * self.mesh.n
        for shard, executor, ck in restored:
            if ck.complete:
                out[shard] = ck.pages[0] if ck.pages else None
            else:
                out[shard] = executor.merge_counted(ck.pages)
                if store is not None:
                    store.save(scope_of(shard), OperatorCheckpoint(
                        scope=scope_of(shard), cursor=ck.cursor,
                        pages=[] if out[shard] is None else [out[shard]],
                        complete=True))
        for shard, executor, pages in dispatched:
            out[shard] = executor.merge_counted(pages)
            if store is not None:
                # merged output replaces the raw page list: the retry
                # restores ONE page per shard, and the raw staging dies
                store.save(scope_of(shard), OperatorCheckpoint(
                    scope=scope_of(shard), cursor=len(pages),
                    pages=[] if out[shard] is None else [out[shard]],
                    complete=True))
            if self._faults is not None:
                # per-shard site AFTER the shard's checkpoint landed: an
                # injected fragment fault costs the remaining shards,
                # never the completed ones (restored shards do no work
                # and pass no site)
                self._faults.site(
                    "fragment",
                    f"fragment-{frag.fragment_id}/shard-{shard}")
        return out

    # ------------------------------------------------------ exchange plane

    def _apply_exchange(self, child_pages: List[Optional[Page]],
                        remote: RemoteSourceNode) -> List[Optional[Page]]:
        self._check_deadline()
        if self._faults is not None:
            self._faults.site("exchange", f"fragment-{remote.fragment_id}")
        n = self.mesh.n
        if self._collector is not None:
            # 'staged' data plane: the producer ran through the per-shard
            # dispatch loop and its outputs were re-staged for this
            # standalone collective (vs. 'fused' in a mesh program).
            # ONE batched count fetch — a per-page device_get would sync
            # every shard's stream separately (the transfer discipline
            # everything else on this path follows)
            from trino_tpu.exec.memory import live_page_bytes
            live = [p for p in child_pages if p is not None]
            counts = [int(c) for c in host_read(
                [p.num_rows for p in live], "exchange_counts")]
            rows = sum(counts)
            nbytes = sum(live_page_bytes(p, c)
                         for p, c in zip(live, counts))
            self._collector.add_exchange("staged", rows, nbytes)
        ref = next((p for p in child_pages if p is not None), None)
        if ref is None:
            return [None] * n
        pages = [_empty_like(p if p is not None else ref)
                 if p is None else p for p in child_pages]
        pages = _normalize_pages(pages)
        global_page = self.mesh.shard_pages(pages)

        if remote.kind == ExchangeKind.REPARTITION:
            lay = {s.name: i for i, s in enumerate(remote.symbols)}
            keys = tuple(lay[s.name] for s in remote.partition_keys)
            cap = pages[0].capacity
            bucket = max(1024, _next_pow2(max(1, cap // n)))
            while True:
                out, overflow = self._exchange_jit(
                    "a2a", keys, bucket)(global_page)
                if int(np.max(np.asarray(host_read(
                        overflow, "exchange_overflow")))) == 0:
                    break
                bucket *= 2
                if bucket > cap:
                    # a shard can never send more than cap rows to one peer
                    out, overflow = self._exchange_jit(
                        "a2a", keys, cap)(global_page)
                    break
            return _unstack_page(out, n)

        # BROADCAST / GATHER / MERGE all materialize the full relation on
        # every shard via all_gather; GATHER consumers are single-shard
        # fragments that read shard 0, MERGE re-sorts below
        out = self._exchange_jit("gather", (), 0)(global_page)
        per_shard = _unstack_page(out, n)
        if remote.kind == ExchangeKind.MERGE and remote.order_by:
            lay = {s.name: i for i, s in enumerate(remote.symbols)}
            sort_keys = tuple(
                SortKey(lay[o.symbol.name], o.ascending, o.nulls_first)
                for o in remote.order_by)
            sort_op = cached_kernel(("merge-sort", sort_keys),
                                    lambda: order_by(list(sort_keys)))
            per_shard = [None if p is None else sort_op(p)
                         for p in per_shard]
        return per_shard

    def _exchange_jit(self, kind: str, keys: tuple, bucket: int):
        key = (kind, keys, bucket)
        fn = self._exchange_jits.get(key)
        if fn is None:
            if kind == "a2a":
                def prog(page):
                    return all_to_all_by_key(page, list(keys), bucket)
            else:
                def prog(page):
                    return broadcast_page(page)
            from trino_tpu.exec.jit_cache import named
            fn = jax.jit(named(self.mesh.shard_map(prog),
                               ("exchange-" + kind,)))
            self._exchange_jits[key] = fn
        return fn


# ---------------------------------------------------------------------------
# page plumbing for the collective data plane


def split_scan_capacity(session, conn, table, splits) -> int:
    """Scan page capacity for a sharded split set: the session page
    floor, grown to the per-split row envelope up to scan_page_capacity.
    Shared by the per-shard dispatch loop and mesh staging so the two
    data planes size identical pages for the same query."""
    cap = int(session.get("page_capacity"))
    try:
        stats = conn.metadata.get_table_statistics(table)
        rows = int(stats.row_count) if stats and stats.row_count else 0
    except Exception:
        rows = 0
    per_split = math.ceil(rows / max(1, len(splits)))
    if per_split > cap:
        max_cap = int(session.get("scan_page_capacity"))
        cap = min(_next_pow2(per_split), max_cap)
    return cap


def _find_remote(node, fragment_id: int) -> RemoteSourceNode:
    if isinstance(node, RemoteSourceNode) and node.fragment_id == fragment_id:
        return node
    for s in node.sources:
        found = _find_remote(s, fragment_id)
        if found is not None:
            return found
    return None


def _empty_like(ref: Page) -> Page:
    cols = tuple(Column(jnp.zeros_like(c.values),
                        None if c.valid is None else jnp.zeros_like(c.valid),
                        c.type, c.dictionary) for c in ref.columns)
    return Page(cols, jnp.asarray(0, dtype=jnp.int32))


def _normalize_pages(pages: List[Page]) -> List[Page]:
    """Make per-shard pages stackable into one global pytree: equal
    capacities, uniform validity-mask presence, and shared dictionaries per
    column (re-encode onto a union pool when shards disagree)."""
    cap = max(p.capacity for p in pages)
    pages = [p.pad_to(_next_pow2(cap)) if p.capacity < cap else p
             for p in pages]
    cap = max(p.capacity for p in pages)
    pages = [p.pad_to(cap) for p in pages]
    ncols = pages[0].num_columns
    out_cols: List[List[Column]] = [list(p.columns) for p in pages]
    for ci in range(ncols):
        cols = [p.column(ci) for p in pages]
        dicts = {id(c.dictionary): c.dictionary for c in cols
                 if c.dictionary is not None}
        remap = None
        union = None
        if len(dicts) > 1:
            union, tables = union_dictionaries(list(dicts.values()))
            remap = {did: tbl for did, tbl in zip(dicts, tables)}
        any_valid = any(c.valid is not None for c in cols)
        for pi, c in enumerate(cols):
            values = c.values
            dictionary = c.dictionary
            if remap is not None and c.dictionary is not None:
                values = jnp.take(remap[id(c.dictionary)],
                                  jnp.clip(values, 0), mode="clip")
                dictionary = union
            valid = c.valid
            if any_valid and valid is None:
                valid = jnp.ones(c.capacity, dtype=jnp.bool_)
            out_cols[pi][ci] = Column(values, valid, c.type, dictionary)
    return [Page(tuple(cs), jnp.asarray(p.num_rows, dtype=jnp.int32))
            for cs, p in zip(out_cols, pages)]


def _unstack_page(global_page: Page, n: int) -> List[Optional[Page]]:
    """View a workers-sharded global Page as per-shard Pages without a host
    round trip: each leaf's addressable shards are the per-device blocks."""
    leaves, treedef = jax.tree_util.tree_flatten(global_page)
    per_shard: List[list] = [[] for _ in range(n)]
    for leaf in leaves:
        shards = sorted(
            leaf.addressable_shards,
            key=lambda s: (s.index[0].start or 0) if s.index else 0)
        if len(shards) != n:
            # replicated or single-device leaf: slice on host
            data = host_read(leaf, "split_shards")
            for k in range(n):
                per_shard[k].append(jnp.asarray(data[k]))
            continue
        for k, s in enumerate(shards):
            per_shard[k].append(jnp.squeeze(s.data, axis=0))
    return [jax.tree_util.tree_unflatten(treedef, ls) for ls in per_shard]
