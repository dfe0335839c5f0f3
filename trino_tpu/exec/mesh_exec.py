"""Mesh co-scheduled fragment execution: one XLA program per stage chain.

The tentpole of multi-chip sharded execution. The per-shard dispatch loop
(exec/distributed.py `_fragment_attempt`) runs each fragment's operator
pipelines shard-by-shard in Python, stages the per-shard outputs, and
applies the consuming exchange as a standalone collective. This module
replaces that for eligible fragment chains: the WHOLE chain — leaf scans
sharded one-shard-per-chip in HBM, filter/project/join/aggregate kernels
per shard, and every inter-fragment exchange as an in-program
`jax.lax.all_to_all` / `all_gather` over the ICI mesh — compiles into ONE
jitted `shard_map` program. Pages never stage through the host between
fragments; all shards execute concurrently under a single dispatch.

Reference parity: this is PlanFragmenter's stage tree executed the way
the SNIPPETS.md references run training steps — `pjit`-style sharding
annotations (NamedSharding over the workers Mesh, placed by
QueryMesh.shard_pages) with collectives as the PartitionedOutputOperator
data plane (SURVEY §7 step 7, the "co-scheduled fragments" round).

Skew (JSPIM): partitioned joins detect globally-heavy probe keys
in-program and switch the exchange pair to spread(probe)/replicate(build)
so one hot key cannot overload a chip (parallel/exchange.py).

Strategy selection: partitioned vs. global GROUP BY is decided by the
CBO at plan time (planner/optimizer._grouped_exchange_kind — "Global
Hash Tables Strike Back"); this module just executes the chosen exchange.

Static shapes: repartition bucket capacities and join output capacities
use the engine's overflow-ladder contract — each site returns its
overflow/true-total as an aux output, and the host re-runs the program
with that site's capacity doubled until everything fits. Programs are
keyed in the jit cache on (canonical structure, ladder, mesh size), so a
repeated query shape dispatches a warm executable. What stays in a key
is a string (expr/hoist.py): a child fragment that evaluates one runs as
a program of its own first and feeds the chain's program its page, so a
new string compiles that fragment and nothing above it.

Fallback: any unsupported node (or chaos runs — per-shard fault sites
must fire) raises MeshUnsupported and the caller transparently uses the
per-shard dispatch loop; the obs exchange counters then record 'staged'
instead of 'fused' exchanges, which is exactly what the mesh test suite
asserts against.

Operator-level stats (round 13) run ON the mesh instead of forcing the
fallback: the converged program dispatch is timed once
(block_until_ready — the program is one XLA call, so the fence is free
at this granularity) and emits PROGRAM-LEVEL operator rows — the wall
apportions across the co-scheduled fragments by their psum'd exchanged
data volume (the in-program cost signal the aux channel already
carries), then equally across each fragment's plan nodes; fragment
roots carry the psum'd rows/bytes that crossed their exchange. Turning
`collect_operator_stats` on no longer changes the data plane:
exchanges_staged stays 0 and the same jitted program dispatches.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.errors import GENERIC_INTERNAL_ERROR, TrinoError
from trino_tpu.exec.jit_cache import cached_kernel, host_read, pulled
from trino_tpu.exec.local_planner import _layout, _next_pow2, lower_expr
from trino_tpu.expr.compiler import compile_expression, compile_filter
from trino_tpu.expr.ir import BoundParam, Call, Literal, SpecialForm
from trino_tpu.ops import (AggSpec, JoinType, SortKey, Step, hash_aggregate,
                           hash_join, order_by, top_n)
from trino_tpu.ops.aggregate import (COLLECT_AGGREGATES, get_aggregate)
from trino_tpu.page import (Column, Page, count_host_staging,
                            defer_compaction, in_chunks, op_scope)
from trino_tpu.parallel.exchange import (AXIS, all_to_all_by_key,
                                         all_to_all_replicate,
                                         broadcast_page, detect_heavy_keys)
from trino_tpu.planner.nodes import (
    AggregationNode, AggStep, ExchangeKind, FilterNode, JoinClause,
    JoinKind, JoinNode, LimitNode, ProjectNode, SemiJoinNode, SortNode,
    Symbol, TableScanNode, TopNNode, WindowNode)
from trino_tpu.planner.optimizer import PlanFragment, RemoteSourceNode


class MeshUnsupported(Exception):
    """This fragment chain cannot lower to one mesh program; the caller
    falls back to the per-shard dispatch loop (not an error)."""


class MeshExecutionError(TrinoError):
    """The co-scheduled program failed to converge (ladder exhausted)."""

    CODE = GENERIC_INTERNAL_ERROR


_MAX_LADDER_ROUNDS = 10

# lanes a partial aggregate with small state (global, or keyed by
# dictionary codes: ops/aggregate's direct path) takes at a time inside a
# mesh program — the local scan's page. The direct path's scatter form
# stacks every state into one [lanes, states] operand, which the TPU tiles
# to 128 columns: a whole 46 M-lane SF30 shard at once asked for 23.6 GB.
# (q1 takes the masked form since PR 29 and stacks nothing; whether the
# chunks are still needed is a question for a later PR.) The walk is
# `page.in_chunks`, the local executor's too (local_planner.compose_walk)
_CHUNK_LANES = 1 << 20


def _bucket_guess(capacity: int, n: int, slack: int = 1) -> int:
    """First per-peer bucket of a hash repartition: an even split of the
    sender's lanes (times `slack`), rounded up to 1 024 lanes and no
    further. A power of two above that made every page behind an exchange
    of an SF30 shard 67 M lanes where 46 M hold it; mix64 spreads a full
    page over its peers to within a few thousand rows, a filtered page has
    room to spare, and what does overflow climbs the ladder once."""
    return max(1024, -(-slack * capacity // (n * 1024)) * 1024)


def _join_output_guess(probe_capacity: int, join_kind) -> int:
    """First output capacity of an in-program join. An outer join keeps
    every probe row; an inner one usually far fewer than the probe page
    has lanes (a shard after an exchange is mostly padding and filtered
    rows: q3's second join at SF30 keeps 0.2 M rows of 46 M lanes), and
    everything behind it — partial aggregate, exchange, final aggregate,
    TopN — sorts and gathers at this many lanes. An eighth of the probe's
    lanes; a join that keeps more reports its true total and climbs the
    ladder once, which is remembered."""
    if join_kind != JoinType.INNER:
        return probe_capacity
    return _bucket_guess(probe_capacity, 8)


class _Env:
    """Per-trace state a lowered closure tree reads: the staged leaf pages
    (positional), the program's hoisted literals (one replicated operand,
    indexed by the expressions' Param slots) and the capacity ladder;
    closures deposit per-site aux scalars (overflow counters, true
    totals, exchanged rows) keyed by static site id — the host reads
    them back to drive the ladder."""

    def __init__(self, pages: Sequence[Page], ladder: Dict[int, int],
                 params: Tuple = ()):
        self.pages = list(pages)
        self.ladder = ladder
        self.params = params
        self.aux: Dict[int, dict] = {}


def _page_row_bytes(page: Page) -> int:
    """Static per-row byte estimate of a page (dtype itemsizes + masks)."""
    total = 0
    for c in page.columns:
        total += c.values.dtype.itemsize
        if c.valid is not None:
            total += 1
    return max(total, 1)


def _exchange_aux(env: _Env, site: int, page: Page, extra: dict) -> None:
    with op_scope("exchange__psum"):
        live = page.num_rows if page.selection is None \
            else jnp.sum(page.row_mask())
        rows = jax.lax.psum(live.astype(jnp.int64), AXIS)
        d = {"rows": rows,
             "bytes": rows * jnp.int64(_page_row_bytes(page))}
    d.update(extra)
    env.aux[site] = d


class MeshLowerer:
    """Lower a PlanFragment tree (+ its consuming exchange) into a single
    per-shard traced function over staged, sharded leaf pages."""

    def __init__(self, session, metadata, n_shards: int, exec_params=()):
        self.session = session
        self.metadata = metadata
        self.n = n_shards
        self.exec_params = tuple(exec_params)
        # the program's hoisted literals and EXECUTE values, in Param
        # slot order: every expression indexes this one list
        self.param_values: List[np.ndarray] = []
        # filters whose page goes, through lane-wise steps only, into a
        # partial aggregate: they hand on a mask (PR 26's rule)
        self._deferred: set = set()
        # plan node id -> the closure that stands for it (the page a
        # chunked chain is fed, instead of the node's own lowering)
        self._stand_in: Dict[int, Callable] = {}
        # what the program is fed, in the order of its page operands: a
        # TableScanNode (staged, or its resident shards), or a (child
        # fragment, remote source) pair that runs as a program of its own
        # first (`_keys_on_a_string`) and hands over its page
        self.leaves: List = []
        self.fed_fragments: set = set()  # ids of those child fragments
        self.sites: List[str] = []       # site id -> kind (a2a | join)
        self.key_parts: List = []        # canonical structure key
        self.exchange_sites: List[int] = []
        # fragment id -> the exchange site that carries ITS output
        # (program-level stats apportion the measured wall by each
        # fragment's psum'd exchanged volume read off these sites)
        self.fragment_sites: Dict[int, int] = {}
        self._skew = bool(session.get("skewed_exchange_enabled"))
        self._skew_k = max(1, int(session.get("skew_heavy_key_limit")))

    # ------------------------------------------------------------ plumbing

    def _key(self, *parts) -> None:
        self.key_parts.append(tuple(parts))

    def _site(self, kind: str) -> int:
        self.sites.append(kind)
        return len(self.sites) - 1

    def _expr(self, e, layout, types):
        """Lower one expression for in-program evaluation, hoisted as on
        the local path (expr/hoist.py): numeric, decimal, date and
        interval literals and non-string EXECUTE values become Param
        slots of `param_values`, so the program key is the literal-free
        tree; strings and NULLs stay in it."""
        from trino_tpu.expr.hoist import hoist_into
        return hoist_into(lower_expr(e, layout, types), self.param_values,
                          self.exec_params)

    def _defer_filters_under(self, node):
        """Mark the filters that reach a consumer reading liveness from
        `row_mask()` alone — a partial aggregate, a hash repartition —
        through lane-wise steps only: they hand on a selection mask and
        move no row (PR 26's rule, read off the plan, no option).
        Returns the node below that chain."""
        while isinstance(node, (FilterNode, ProjectNode)):
            if isinstance(node, FilterNode):
                self._deferred.add(id(node))
            node = node.source
        return node

    def _lower_producer(self, frag: PlanFragment, kind: str) -> Callable:
        """A fragment's root, as its consuming exchange takes it: a hash
        repartition drops dead rows by `row_mask()` while it buckets them,
        so filters straight under it need not compact first (q3's three
        scans); a broadcast reads rows by position and gets them dense."""
        if kind == ExchangeKind.REPARTITION:
            self._defer_filters_under(frag.root)
        return self.lower_node(frag.root, frag)

    def _lower_child_producer(self, child: PlanFragment,
                              remote: RemoteSourceNode) -> Callable:
        """A child fragment's root inside this program — or, where the
        child keys on a string, a leaf page that the child's own program
        hands over (`_co_schedule` runs it first, without its exchange,
        which stays here). The string is then in the key of the program
        that evaluates it and of no other (expr/hoist.py, M4): q3's joins,
        GROUP BY and TopN, minutes of compile at SF30, are one executable
        for every SEGMENT, and a new SEGMENT compiles a filter."""
        if not _keys_on_a_string(child):
            return self._lower_producer(child, remote.kind)
        idx = len(self.leaves)
        self.leaves.append((child, remote))
        self.fed_fragments.add(child.fragment_id)
        self._key("fed", remote.kind,
                  tuple((s.name, s.type) for s in child.root.outputs))
        return lambda env: env.pages[idx]

    @staticmethod
    def _scoped(name: str, src: Callable, op: Callable) -> Callable:
        """fn(env) running `op` on `src`'s page under `name`, a
        `<family>__<tag>` scope: the source's own ops keep theirs."""
        def fn(env: _Env):
            page = src(env)
            with op_scope(name):
                return op(page)
        return fn

    # ------------------------------------------------------------- entry

    def lower_child(self, frag: PlanFragment, remote: RemoteSourceNode,
                    exchange: bool = True) -> Callable:
        """The co-scheduled unit: child fragment tree + its consuming
        exchange. Returns fn(env) -> per-shard Page (post-exchange; the
        producer's own page where `exchange` is off)."""
        inner = self._lower_producer(frag, remote.kind)
        if not exchange:
            return inner
        return self._lower_exchange(inner, remote.kind,
                                    remote.partition_keys, remote.order_by,
                                    tuple(frag.root.outputs),
                                    frag_id=frag.fragment_id)

    # ----------------------------------------------------------- exchange

    def _lower_exchange(self, inner: Callable, kind: str, partition_keys,
                        ordering, symbols: Tuple[Symbol, ...],
                        frag_id: Optional[int] = None) -> Callable:
        self._key("exchange", kind,
                  tuple(s.name for s in partition_keys))
        if kind == ExchangeKind.REPARTITION:
            lay = {s.name: i for i, s in enumerate(symbols)}
            keys = tuple(lay[s.name] for s in partition_keys)
            site = self._site("a2a")
            self.exchange_sites.append(site)
            if frag_id is not None:
                self.fragment_sites[frag_id] = site

            def fn(env: _Env) -> Page:
                page = inner(env)
                n = jax.lax.psum(1, AXIS)
                bucket = env.ladder.get(site) or \
                    _bucket_guess(page.capacity, n)
                out, overflow = all_to_all_by_key(page, list(keys), bucket)
                _exchange_aux(env, site, page,
                              {"overflow": overflow,
                               "bucket": jnp.int32(bucket)})
                return out
            return fn

        # BROADCAST / GATHER / MERGE: materialize the full relation on
        # every shard (GATHER consumers read shard 0's replica)
        site = self._site("bcast")
        self.exchange_sites.append(site)
        if frag_id is not None:
            self.fragment_sites[frag_id] = site
        sort_op = None
        if kind == ExchangeKind.MERGE and ordering:
            lay = {s.name: i for i, s in enumerate(symbols)}
            sort_keys = [SortKey(lay[o.symbol.name], o.ascending,
                                 o.nulls_first) for o in ordering]
            self._key("merge-sort", tuple(sort_keys))
            sort_op = order_by(sort_keys)

        def fn(env: _Env) -> Page:
            page = inner(env)
            out = broadcast_page(page)
            if sort_op is not None:
                with op_scope("sort__merge_sort"):
                    out = sort_op(out)
            _exchange_aux(env, site, page, {})
            return out
        return fn

    # ------------------------------------------------------------- nodes

    def lower_node(self, node, frag: PlanFragment
                   ) -> Callable:
        if id(node) in self._stand_in:
            return self._stand_in[id(node)]
        name = type(node).__name__
        method = getattr(self, f"_lower_{name}", None)
        if method is None:
            raise MeshUnsupported(f"no mesh lowering for {name}")
        return method(node, frag)

    def _lower_TableScanNode(self, node: TableScanNode, frag) -> Callable:
        idx = len(self.leaves)
        self.leaves.append(node)
        # the table, not its handle: a pushed-down constraint or limit
        # changes what is staged (shapes and dictionaries are the
        # executable's signature), never the program
        self._key("scan", node.catalog, str(node.table.name),
                  tuple(s.name for s, _ in node.assignments))
        return lambda env: env.pages[idx]

    def _lower_RemoteSourceNode(self, node: RemoteSourceNode, frag
                                ) -> Callable:
        child = next((c for c in frag.children
                      if c.fragment_id == node.fragment_id), None)
        if child is None:
            raise MeshUnsupported(f"missing child {node.fragment_id}")
        inner = self._lower_child_producer(child, node)
        return self._lower_exchange(inner, node.kind, node.partition_keys,
                                    node.order_by,
                                    tuple(child.root.outputs),
                                    frag_id=child.fragment_id)

    def _lower_FilterNode(self, node: FilterNode, frag) -> Callable:
        src = self.lower_node(node.source, frag)
        lay, typ = _layout(node.source.outputs)
        pred = self._expr(node.predicate, lay, typ)
        deferred = id(node) in self._deferred
        self._key("filter", pred, deferred)
        f = compile_filter(pred)

        def fn(env: _Env) -> Page:
            page = src(env)
            with op_scope("scan_filter__filter"), \
                    defer_compaction(deferred):
                return page.filter(f(page, env.params))
        return fn

    def _lower_ProjectNode(self, node: ProjectNode, frag) -> Callable:
        src = self.lower_node(node.source, frag)
        lay, typ = _layout(node.source.outputs)
        exprs = tuple(self._expr(e, lay, typ)
                      for _, e in node.assignments)
        self._key("project", exprs)
        fns = [compile_expression(e) for e in exprs]

        def fn(env: _Env) -> Page:
            page = src(env)
            with op_scope("scan_filter__project"):
                return Page(tuple(f(page, env.params) for f in fns),
                            page.num_rows, page.selection)
        return fn

    def _lower_LimitNode(self, node: LimitNode, frag) -> Callable:
        if not node.partial:
            raise MeshUnsupported("non-partial LIMIT in sharded fragment")
        src = self.lower_node(node.source, frag)
        self._key("limit", node.count)

        def fn(env: _Env) -> Page:
            page = src(env)
            with op_scope("scan_filter__limit"):
                rows = jnp.minimum(page.num_rows,
                                   jnp.int32(node.count)).astype(jnp.int32)
                return Page(page.columns, rows)
        return fn

    def _lower_TopNNode(self, node: TopNNode, frag) -> Callable:
        if node.step == "final":
            raise MeshUnsupported("final TopN in sharded fragment")
        src = self.lower_node(node.source, frag)
        lay, _ = _layout(node.source.outputs)
        keys = [SortKey(lay[o.symbol.name], o.ascending, o.nulls_first)
                for o in node.order_by]
        self._key("topn", node.count, tuple(keys))
        op = top_n(node.count, keys)
        # the kept rows are a prefix of at most `count`: hand on that
        # much and not the input's lanes (a GATHER above this multiplies
        # its input's capacity by the mesh — q3 at SF30: 67 M lanes into
        # 268 M, 10.7 GB a chip, for ten rows)
        keep = max(1024, _next_pow2(node.count))
        return self._scoped("sort__topn", src,
                            lambda page: op(page).shrink_to(keep))

    def _lower_SortNode(self, node: SortNode, frag) -> Callable:
        src = self.lower_node(node.source, frag)
        lay, _ = _layout(node.source.outputs)
        keys = [SortKey(lay[o.symbol.name], o.ascending, o.nulls_first)
                for o in node.order_by]
        self._key("sort", tuple(keys))
        return self._scoped("sort__order_by", src, order_by(keys))

    def _lower_WindowNode(self, node: WindowNode, frag) -> Callable:
        from trino_tpu.exec.local_planner import LocalExecutionPlanner
        from trino_tpu.ops.window import WindowSpec, window
        src = self.lower_node(node.source, frag)
        lay, typ = _layout(node.source.outputs)
        part = tuple(lay[s.name] for s in node.partition_by)
        okeys = tuple(SortKey(lay[o.symbol.name], o.ascending,
                              o.nulls_first) for o in node.order_by)
        specs = []
        for out_sym, wf in node.functions:
            try:
                whole, bounds = LocalExecutionPlanner._lower_frame(node, wf)
            except Exception as e:
                raise MeshUnsupported(f"window frame: {e}")
            args = []
            for a in wf.args:
                if a.__class__.__name__ != "SymbolRef":
                    raise MeshUnsupported("window args must be symbols")
                args.append(lay[a.name])
            specs.append(WindowSpec(wf.name.lower(), tuple(args),
                                    out_sym.type, whole,
                                    wf.frame_type == "ROWS", bounds))
        self._key("window", part, okeys, tuple(specs))
        return self._scoped("window__window", src,
                            window(part, okeys, specs))

    # -------------------------------------------------------- aggregation

    def _agg_specs(self, node: AggregationNode, lay) -> Tuple[AggSpec, ...]:
        specs = []
        for _out, call in node.aggregations:
            if call.args:
                arg = call.args[0]
                input_ch = lay[arg.name] if lay is not None else None
                in_type = call.input_type
            else:
                input_ch, in_type = None, None
            in2_ch = in2_type = None
            if len(call.args) > 1 and lay is not None:
                arg2 = call.args[1]
                in2_ch, in2_type = lay[arg2.name], arg2.type
            mask_ch = None
            if call.filter is not None:
                if lay is None:
                    raise MeshUnsupported("FILTER agg in final step")
                mask_ch = lay[call.filter.name]
            specs.append(AggSpec(call.name, input_ch, in_type, mask_ch,
                                 call.distinct, in2_ch, in2_type))
        return tuple(specs)

    def _lower_AggregationNode(self, node: AggregationNode, frag
                               ) -> Callable:
        if node.step == AggStep.PARTIAL:
            return self._lower_partial_aggregation(node, frag)
        src = self.lower_node(node.source, frag)
        if node.step == AggStep.FINAL:
            specs = self._agg_specs(node, None)
            nkeys = len(node.group_by)
            state_channels = []
            ch = nkeys
            for spec in specs:
                fn = get_aggregate(spec.name, spec.input_type)
                k = len(fn.state(spec.input_type))
                state_channels.append(list(range(ch, ch + k)))
                ch += k
            self._key("agg-final", nkeys, specs)
            return self._scoped(
                "aggregate__final", src,
                hash_aggregate(list(range(nkeys)), list(specs),
                               Step.FINAL, state_channels))
        # SINGLE (DISTINCT / single-step aggs after a repartition): the
        # sort-based kernel needs every row of a group in one call — the
        # exchange guarantees that. Collect aggregates additionally need
        # a host-measured list length; bail to the dispatch loop.
        if any(call.name in COLLECT_AGGREGATES
               for _, call in node.aggregations):
            raise MeshUnsupported("collect aggregate needs host sizing")
        lay, _ = _layout(node.source.outputs)
        keys = tuple(lay[s.name] for s in node.group_by)
        specs = self._agg_specs(node, lay)
        self._key("agg-single", keys, specs)
        return self._scoped(
            "aggregate__single", src,
            hash_aggregate(list(keys), list(specs), Step.SINGLE))

    def _lower_partial_aggregation(self, node: AggregationNode, frag
                                   ) -> Callable:
        """The partial aggregate and the lane-wise steps under it (filter,
        project) as one chain over the page below them, as the local
        planner fuses them. It reads liveness from row_mask() alone, so
        the chain's filters hand it a selection mask and move no row
        (`_defer_filters_under`: q1). Where its state is small the chain
        runs a chunk of lanes at a time (`page.in_chunks`)."""
        from trino_tpu.ops.aggregate import partial_states_are_few
        below = self._defer_filters_under(node.source)
        base = self.lower_node(below, frag)
        fed: List[Page] = []        # the chain's input, while it is traced
        self._stand_in[id(below)] = lambda env: fed[-1]
        try:
            chain = self.lower_node(node.source, frag)
        finally:
            del self._stand_in[id(below)]
        lay, _ = _layout(node.source.outputs)
        keys = tuple(lay[s.name] for s in node.group_by)
        specs = self._agg_specs(node, lay)
        self._key("agg-partial", keys, specs, _CHUNK_LANES)
        op = hash_aggregate(list(keys), list(specs), Step.PARTIAL)

        def fn(env: _Env) -> Page:
            def run_chain(page: Page) -> Page:
                fed.append(page)
                try:
                    return chain(env)
                finally:
                    fed.pop()

            def body(page: Page) -> Page:
                page = run_chain(page)
                with op_scope("aggregate__partial"):
                    return op(page)
            page = base(env)
            # what the chain hands the aggregate, by shape alone: whether
            # its state is small hangs on the key columns' dictionaries,
            # which are the chain's to make
            fed_shape = jax.eval_shape(run_chain, page)
            if not partial_states_are_few(fed_shape, keys, specs):
                return body(page)
            with op_scope("aggregate__partial_merge"):
                cap = page.capacity
                if cap <= _CHUNK_LANES or cap % _CHUNK_LANES:
                    return body(page)
                return in_chunks(page, body, _CHUNK_LANES)
        return fn

    # -------------------------------------------------------------- joins

    def _lower_JoinNode(self, node: JoinNode, frag) -> Callable:
        if node.kind == JoinKind.RIGHT:
            flipped = JoinNode(
                JoinKind.LEFT, node.right, node.left,
                tuple(JoinClause(c.right, c.left) for c in node.criteria),
                node.filter, node.distribution)
            inner = self._lower_JoinNode(flipped, frag)
            out_syms = node.left.outputs + node.right.outputs
            lay, _ = _layout(flipped.outputs)
            order = tuple(lay[s.name] for s in out_syms)
            self._key("select", order)
            return self._scoped("join__select", inner, lambda p: Page(
                tuple(p.columns[c] for c in order), p.num_rows))
        if node.kind not in (JoinKind.INNER, JoinKind.LEFT):
            raise MeshUnsupported(f"{node.kind} join")
        join_kind = JoinType.INNER if node.kind == JoinKind.INNER \
            else JoinType.LEFT

        probe_syms = node.left.outputs
        build_syms = node.right.outputs
        probe_lay, _ = _layout(probe_syms)
        build_lay, _ = _layout(build_syms)
        probe_keys = tuple(probe_lay[c.left.name] for c in node.criteria)
        build_keys = tuple(build_lay[c.right.name] for c in node.criteria)
        out_symbols = node.outputs
        out_names = {s.name for s in out_symbols}
        probe_keep = tuple(i for i, s in enumerate(probe_syms)
                           if s.name in out_names)
        build_keep = tuple(i for i, s in enumerate(build_syms)
                           if s.name in out_names)

        post_pred = None
        if node.filter is not None:
            if join_kind != JoinType.INNER:
                raise MeshUnsupported("outer join residual filter")
            lay, typ = _layout(out_symbols)
            post_pred = self._expr(node.filter, lay, typ)
        post_filter = None if post_pred is None else \
            compile_filter(post_pred)

        # co-partitioned join: both inputs repartition on the clause keys
        # — fuse the exchange pair into this join and, when enabled, make
        # it skew-aware (heavy probe keys spread, their build rows
        # replicate: JSPIM). Otherwise the children lower normally (their
        # own exchanges apply inside).
        sides = self._co_partitioned_inputs(node, frag, join_kind)
        if sides is not None:
            probe_fn, build_fn, ppre_keys, bpre_keys, psite, bsite = sides
        else:
            probe_fn = self.lower_node(node.left, frag)
            build_fn = self.lower_node(node.right, frag)
            ppre_keys = bpre_keys = None
            psite = bsite = None

        site = self._site("join")
        self._key("join", probe_keys, build_keys, join_kind, post_pred,
                  probe_keep, build_keep)

        def fn(env: _Env) -> Page:
            if psite is None:
                probe = probe_fn(env)
                build = build_fn(env)
            else:
                probe, build = self._apply_skewed_pair(
                    env, probe_fn, build_fn, ppre_keys, bpre_keys,
                    psite, bsite)
            cap = env.ladder.get(site) or \
                _join_output_guess(probe.capacity, join_kind)
            with op_scope("join__hash_join"):
                probe = _align_key_dictionaries(probe, build, probe_keys,
                                                build_keys)
                op = hash_join(list(probe_keys), list(build_keys),
                               join_kind, output_capacity=cap,
                               prepared=False,
                               probe_out=probe_keep, build_out=build_keep)
                out, total = op(probe, build)
                # per-shard: the host takes the max over shards
                # (_ladder_bumps); an in-program pmax over int64 is a
                # collective the TPU compiler does not lower (only a
                # 64-bit SUM all-reduce is)
                aux = {"total": total.astype(jnp.int64),
                       "cap": jnp.int32(cap)}
            if post_filter is not None:
                with op_scope("join__post_filter"):
                    out = out.filter(post_filter(out, env.params))
            env.aux[site] = aux
            return out
        return fn

    def _co_partitioned_inputs(self, node: JoinNode, frag, join_kind):
        left, right = node.left, node.right
        if not (isinstance(left, RemoteSourceNode)
                and isinstance(right, RemoteSourceNode)
                and left.kind == ExchangeKind.REPARTITION
                and right.kind == ExchangeKind.REPARTITION):
            return None
        lchild = next((c for c in frag.children
                       if c.fragment_id == left.fragment_id), None)
        rchild = next((c for c in frag.children
                       if c.fragment_id == right.fragment_id), None)
        if lchild is None or rchild is None:
            return None
        # the partition keys must be exactly the join clause keys, in
        # clause order, for spread/replicate to preserve join semantics
        if tuple(s.name for s in left.partition_keys) != \
                tuple(c.left.name for c in node.criteria) or \
                tuple(s.name for s in right.partition_keys) != \
                tuple(c.right.name for c in node.criteria):
            return None
        probe_fn = self._lower_child_producer(lchild, left)
        build_fn = self._lower_child_producer(rchild, right)
        play = {s.name: i for i, s in enumerate(left.symbols)}
        blay = {s.name: i for i, s in enumerate(right.symbols)}
        ppre = tuple(play[s.name] for s in left.partition_keys)
        bpre = tuple(blay[s.name] for s in right.partition_keys)
        psite = self._site("a2a")
        bsite = self._site("a2a")
        self.exchange_sites += [psite, bsite]
        self.fragment_sites[lchild.fragment_id] = psite
        self.fragment_sites[rchild.fragment_id] = bsite
        self._key("skewed-pair", ppre, bpre, self._skew, self._skew_k)
        return probe_fn, build_fn, ppre, bpre, psite, bsite

    def _apply_skewed_pair(self, env: _Env, probe_fn, build_fn,
                           ppre_keys, bpre_keys, psite, bsite):
        probe_pre = probe_fn(env)
        build_pre = build_fn(env)
        n = jax.lax.psum(1, AXIS)
        pbucket = env.ladder.get(psite) or \
            _bucket_guess(probe_pre.capacity, n)
        bbucket = env.ladder.get(bsite) or \
            _bucket_guess(build_pre.capacity, n, slack=2)
        heavy = None
        if self._skew:
            heavy = detect_heavy_keys(probe_pre, list(ppre_keys),
                                      self._skew_k,
                                      max(pbucket // 2, 1024))
        probe, p_ovf = all_to_all_by_key(probe_pre, list(ppre_keys),
                                         pbucket, heavy=heavy)
        if heavy is not None:
            build, b_ovf = all_to_all_replicate(build_pre, list(bpre_keys),
                                                bbucket, heavy)
        else:
            build, b_ovf = all_to_all_by_key(build_pre, list(bpre_keys),
                                             bbucket)
        _exchange_aux(env, psite, probe_pre,
                      {"overflow": p_ovf, "bucket": jnp.int32(pbucket)})
        _exchange_aux(env, bsite, build_pre,
                      {"overflow": b_ovf, "bucket": jnp.int32(bbucket)})
        return probe, build

    def _lower_SemiJoinNode(self, node: SemiJoinNode, frag) -> Callable:
        probe_fn = self.lower_node(node.source, frag)
        build_fn = self.lower_node(node.filtering_source, frag)
        probe_lay, _ = _layout(node.source.outputs)
        build_lay, _ = _layout(node.filtering_source.outputs)
        probe_keys = tuple(probe_lay[s.name] for s in node.source_keys)
        build_keys = tuple(build_lay[s.name] for s in node.filtering_keys)
        site = self._site("join")
        self._key("semijoin", probe_keys, build_keys, node.null_aware)

        def fn(env: _Env) -> Page:
            probe = probe_fn(env)
            build = build_fn(env)
            cap = env.ladder.get(site) or probe.capacity
            with op_scope("join__semijoin"):
                probe = _align_key_dictionaries(probe, build, probe_keys,
                                                build_keys)
                op = hash_join(list(probe_keys), list(build_keys),
                               JoinType.MARK, output_capacity=cap,
                               prepared=False,
                               null_aware=node.null_aware)
                out, total = op(probe, build)
                # per-shard total, as in the join lowering above
                aux = {"total": total.astype(jnp.int64),
                       "cap": jnp.int32(cap)}
            env.aux[site] = aux
            return out
        return fn

    def _lower_AssignUniqueIdNode(self, node, frag) -> Callable:
        src = self.lower_node(node.source, frag)
        self._key("assign-unique-id")

        def op(page: Page) -> Page:
            base = jax.lax.axis_index(AXIS).astype(jnp.int64) << 44
            idx = jnp.arange(page.capacity, dtype=jnp.int64) + base
            col = Column(idx, None, T.BIGINT, None)
            return Page(tuple(page.columns) + (col,), page.num_rows)
        return self._scoped("scan_filter__assign_unique_id", src, op)


def _align_key_dictionaries(probe: Page, build: Page, probe_keys,
                            build_keys) -> Page:
    """String join keys across distinct dictionaries: remap probe codes
    onto the build pool at trace time (dictionaries are static aux data,
    so the remap table is a host fold — the in-program analog of
    LocalExecutionPlanner._align_join_dictionaries). Probe values absent
    from the build pool map past the pool end and can never match."""
    cols = list(probe.columns)
    changed = False
    for pk, bk in zip(probe_keys, build_keys):
        pc = cols[pk]
        bd = build.columns[bk].dictionary
        if bd is None or pc.dictionary is None or pc.dictionary == bd:
            continue
        pvals = pc.dictionary.values
        n_b = len(bd.values)
        if n_b:
            codes = np.minimum(np.searchsorted(bd.values, pvals),
                               n_b - 1).astype(np.int64)
            present = bd.values[codes] == pvals
        else:
            codes = np.zeros(len(pvals), np.int64)
            present = np.zeros(len(pvals), bool)
        out = np.where(present, codes,
                       n_b + np.arange(len(pvals), dtype=np.int64))
        tbl = jnp.asarray(out.astype(np.int32))
        cols[pk] = Column(jnp.take(tbl, jnp.clip(pc.values, 0),
                                   mode="clip"),
                          pc.valid, pc.type, bd)
        changed = True
    return Page(tuple(cols), probe.num_rows) if changed else probe


# ---------------------------------------------------------------------------
# staging + program driver


def read_shard_pages(mesh, conn, splits, columns, cap: int,
                     on_page=None) -> List[Optional[Page]]:
    """One merged page per shard (None: the shard has no split), shard i's
    made ON chip i: its splits (`part % n == i`, the
    SourcePartitionedScheduler assignment) are pulled with that chip as
    JAX's default device, so a connector that generates on the device
    (tpch) or stages from the host puts the rows where the shard runs —
    never through chip 0, and never more than one shard on a chip.
    `on_page(page, host_bytes)` sees every page pulled."""
    from trino_tpu.page import device_concat
    n = mesh.n
    per_shard: List[Optional[Page]] = []
    for shard in range(n):
        device = mesh.device_of(shard)
        with jax.default_device(device):
            pages: List[Page] = []
            for split in (s for s in splits if s.part % n == shard):
                for page, moved in count_host_staging(pulled(
                        conn.page_source.pages(split, columns, cap),
                        "connector")):
                    if on_page is not None:
                        on_page(page, moved)
                    pages.append(page)
            if not pages:
                per_shard.append(None)
                continue
            if len(pages) > 1:
                key = ("mesh-sconcat", tuple(p.capacity for p in pages),
                       pages[0].num_columns)
                op = cached_kernel(key,
                                   lambda: lambda *ps: device_concat(ps))
                pages = [op(*pages)]
            per_shard.append(jax.device_put(pages[0], device))
    return per_shard


def stage_shards(runner, conn, handle, columns, on_page=None
                 ) -> Tuple[Page, List[int]]:
    """A table's splits as a workers-sharded global Page (leading axis =
    the mesh), shard i read on chip i, and the bytes that put on each
    chip. Raises MeshUnsupported for a table without rows."""
    from trino_tpu.exec.distributed import split_scan_capacity
    mesh = runner.mesh
    splits = conn.split_manager.get_splits(handle, target_splits=mesh.n)
    cap = split_scan_capacity(runner.session, conn, handle, splits)
    return _place(mesh, read_shard_pages(mesh, conn, splits, columns, cap,
                                         on_page), handle)


def _place(mesh, per_shard: List[Optional[Page]], what
           ) -> Tuple[Page, List[int]]:
    from trino_tpu.exec.distributed import _empty_like, _normalize_pages
    from trino_tpu.exec.memory import page_bytes
    ref = next((p for p in per_shard if p is not None), None)
    if ref is None:
        raise MeshUnsupported(f"empty table {what}")
    per_shard = _normalize_pages(
        [_empty_like(ref) if p is None else p for p in per_shard])
    return mesh.shard_pages(per_shard), [page_bytes(p) for p in per_shard]


def admit_shards(cache, tkey, names: Sequence[str], page: Page,
                 collector=None, gen: Optional[int] = None) -> bool:
    """Keep a staged global Page as the table's resident shards."""
    rows = int(np.sum(host_read(page.num_rows, "shard_rows", collector)))
    return rows > 0 and cache.admit_sharded(tkey, names, page, rows,
                                            collector=collector, gen=gen)


def _stage_scan(runner, node: TableScanNode) -> Tuple[Page, List[int]]:
    """One leaf scan as a workers-sharded global Page and the bytes it
    put on each chip to make it.

    Resident shards (exec/table_cache.ShardedTable: the server's table
    warm-up on a mesh runner, or an earlier mesh scan's promotion): the
    program is handed the resident arrays themselves — nothing is
    generated, copied between chips or staged from the host, and
    `mesh_scan_moved_bytes` stays 0.

    Otherwise shard i's splits are read on chip i (`read_shard_pages`)
    and, once the table is hot enough, kept where they are as its
    resident shards; a full-length table-cache entry (promoted by the
    dispatch loop) is sliced by row range and each slice copied to its
    chip. Both count what they made or moved."""
    import dataclasses as _dc

    from trino_tpu.exec.memory import page_bytes
    from trino_tpu.predicate import TupleDomain
    conn = runner.metadata.connector(node.catalog)
    columns = [c for _, c in node.assignments]
    names = [c.name for c in columns]
    mesh = runner.mesh
    col = runner._collector
    st = node.table.name
    tkey = (node.catalog, st.schema, st.table)
    tcache = None if node.catalog == "system" \
        else runner._active_table_cache()
    tgen = None if tcache is None else tcache.generation()

    def moved(staged: Tuple[Page, List[int]]) -> Tuple[Page, List[int]]:
        if col is not None:
            col.mesh_scan_moved_bytes += sum(staged[1])
        return staged

    if tcache is not None:
        resident = None if node.table.limit is not None \
            else tcache.lookup_sharded(tkey, names, mesh.n)
        if resident is not None:
            if col is not None:
                col.table_cache_hit()
            return resident.page(names), [0] * mesh.n
        entry = tcache.lookup(tkey, names)
        if entry is not None:
            if col is not None:
                col.table_cache_hit()
            from trino_tpu.exec.table_cache import build_shard_pages
            return moved(_place(mesh, build_shard_pages(entry, names,
                                                        mesh.n), node.table))
        if col is not None:
            col.table_cache_miss()
    handle = node.table
    prunes = getattr(conn.metadata, "supports_zone_maps", False)
    if prunes and not bool(
            runner.session.get("lake_zone_maps_enabled")):
        handle = _dc.replace(handle, constraint=TupleDomain.all())
    try:
        staged = moved(stage_shards(
            runner, conn, handle, columns,
            None if col is None else
            lambda page, host: col.add_scan_staging(page_bytes(page), host)))
    finally:
        take = getattr(conn, "take_scan_stats", None)
        if take is not None:
            d = take() or {}
            if col is not None and d:
                col.add_pruned(d.get("files_pruned", 0),
                               d.get("row_groups_pruned", 0))
    if tcache is not None and node.table.limit is None \
            and (not prunes or handle.constraint.is_all()) \
            and tcache.note_scan(tkey, names) >= max(1, int(
                runner.session.get("table_cache_min_scans"))):
        # hot-set promotion: the shards stay where this scan made them
        admit_shards(tcache, tkey, names, staged[0], col, tgen)
    return staged


# converged capacities of every program shape this process has run, by
# structure key: the next query of the shape starts there instead of at
# the first guess (a later overflow still climbs; nothing ever shrinks)
_LADDERS: Dict[tuple, Dict[int, int]] = {}
_LADDERS_LOCK = threading.Lock()


def run_co_scheduled(runner, frag: PlanFragment,
                     remote: RemoteSourceNode) -> List[Optional[Page]]:
    """Execute `frag` (and its whole child tree) plus the consuming
    exchange as ONE jitted shard_map program over the runner's mesh — but
    for child fragments that key on a string, each a program of its own
    run first. Returns per-shard post-exchange pages for the parent
    fragment, or raises MeshUnsupported for the dispatch-loop fallback."""
    from trino_tpu.exec.distributed import _unstack_page
    from trino_tpu.exec.memory import page_bytes
    per_shard = _unstack_page(_co_schedule(runner, frag, remote),
                              runner.mesh.n)
    # per-chip peak accounting for the exchange outputs the parent will
    # consume (reserve+free: the gauge is the peak, the pages themselves
    # are owned by XLA until the parent materializes results)
    ledger = runner._memory
    if ledger is not None:
        for shard, p in enumerate(per_shard):
            if p is not None:
                nbytes = page_bytes(p)
                ledger.reserve(nbytes, "mesh-exchange", device=shard)
                ledger.free(nbytes, "mesh-exchange", device=shard)
    return per_shard


def _co_schedule(runner, frag: PlanFragment, remote: RemoteSourceNode,
                 exchange: bool = True) -> Page:
    """One co-scheduled program: `frag`'s tree and (but for a fragment
    that feeds a program above it) its consuming exchange, as the
    workers-sharded global Page it hands on."""
    from trino_tpu.obs.stats import maybe_phase
    mesh = runner.mesh
    lowerer = MeshLowerer(runner.session, runner.metadata, mesh.n,
                          runner._exec_params)
    # may raise MeshUnsupported
    top_fn = lowerer.lower_child(frag, remote, exchange)

    runner._check_deadline()
    col = runner._collector
    # the children that are programs of their own, before anything of
    # this one is staged: what each hands on is a leaf page here
    fed = {i: _co_schedule(runner, *leaf, exchange=False)
           for i, leaf in enumerate(lowerer.leaves)
           if not isinstance(leaf, TableScanNode)}
    staged: List[Page] = []
    staged_bytes: List[List[int]] = []
    with maybe_phase(col, "mesh_stage"):
        for i, leaf in enumerate(lowerer.leaves):
            page, nbytes = (fed[i], [0] * mesh.n) if i in fed \
                else _stage_scan(runner, leaf)
            staged.append(page)
            staged_bytes.append(nbytes)

    ledger = runner._memory
    reserved: List[Tuple[int, int]] = []
    if ledger is not None:
        # what this query put on the chips; resident shards are the
        # table cache's (reserved there, per chip, at admission)
        for per_shard in staged_bytes:
            for shard, nbytes in enumerate(per_shard):
                if nbytes:
                    ledger.reserve(nbytes, "mesh-stage", device=shard)
                    reserved.append((nbytes, shard))

    struct_key = ("mesh-prog", tuple(lowerer.key_parts), mesh.n)
    params = tuple(lowerer.param_values)
    stats_on = col is not None and col.operator_level
    program_wall = 0.0
    with _LADDERS_LOCK:
        ladder: Dict[int, int] = dict(_LADDERS.get(struct_key, ()))
    if col is not None:
        col.mesh_programs += 1
        col.mesh_params += len(params)
    try:
        for _round in range(_MAX_LADDER_ROUNDS):
            runner._check_deadline()
            pre_device = col.device_time_s if stats_on else 0.0
            if col is not None:
                col.mesh_program_rounds += 1
            out_global, aux = _run_program(
                runner, top_fn, staged, struct_key, ladder, params)
            if stats_on:
                # the round's device wall: the program is ONE XLA call,
                # and the jit cache's fenced dispatch timed it (clock
                # stopped at block_until_ready — BEFORE the aux host
                # transfer and the ladder's NumPy analysis, compile wall
                # out) and added it to the query's device time. The
                # CONVERGED round's wall is what the operators share.
                round_wall = col.device_time_s - pre_device
            host_aux = host_read(aux, "mesh_program_aux", col)
            bumps = _ladder_bumps(lowerer, host_aux)
            if not bumps:
                if stats_on:
                    program_wall = round_wall
                break
            ladder.update(bumps)
        else:
            raise MeshExecutionError(
                "mesh program capacity ladder did not converge "
                f"(ladder={ladder})")
    finally:
        if ledger is not None:
            for nbytes, shard in reserved:
                ledger.free(nbytes, "mesh-stage", device=shard)
    if ladder:
        with _LADDERS_LOCK:
            kept = _LADDERS.setdefault(struct_key, {})
            for site, cap in ladder.items():
                kept[site] = max(cap, kept.get(site, 0))

    if col is not None:
        col.mesh_devices = mesh.n
        for site in lowerer.exchange_sites:
            d = host_aux.get(site, {})
            col.add_exchange(
                "fused",
                rows=int(np.max(np.asarray(d.get("rows", 0)))),
                nbytes=int(np.max(np.asarray(d.get("bytes", 0)))))
    if stats_on:
        _record_program_stats(col, lowerer, frag, program_wall, host_aux)
    return out_global


def _collect_fragments(frag: PlanFragment, but: frozenset = frozenset()
                       ) -> List[PlanFragment]:
    """`frag` and the fragments under it, less the trees of those in
    `but` (fragment ids)."""
    out = [frag]
    for child in frag.children:
        if child.fragment_id not in but:
            out.extend(_collect_fragments(child, but))
    return out


def _plan_nodes(node) -> List:
    out = [node]
    for s in node.sources:
        out.extend(_plan_nodes(s))
    return out


def _holds_string(x) -> bool:
    if isinstance(x, (Literal, BoundParam)):
        return T.is_string(x.type) and getattr(x, "value", "") is not None
    if isinstance(x, (Call, SpecialForm)):
        return _holds_string(x.args)
    return isinstance(x, (tuple, list)) and any(map(_holds_string, x))


def _keys_on_a_string(frag: PlanFragment) -> bool:
    """True where a node of the fragment itself (its children answer for
    themselves) evaluates an expression with a string literal or string
    EXECUTE value in it: what expr/hoist.py leaves in a program's key, so
    that each value compiles the program that holds it."""
    return any(_holds_string(v) for n in _plan_nodes(frag.root)
               for v in vars(n).values())


def _record_program_stats(col, lowerer: MeshLowerer, frag: PlanFragment,
                          wall_s: float, host_aux: Dict[int, dict]
                          ) -> None:
    """Program-level operator rows for a co-scheduled mesh program: the
    measured program wall apportions across the co-scheduled fragments
    by their psum'd exchanged data volume (rows + bytes off each
    fragment's exchange-site aux — the cost signal the program already
    computes in-program and psums across chips), then equally across
    each fragment's plan nodes. Fragment roots additionally carry the
    global rows/bytes that crossed their exchange, so
    `collect_operator_stats` on a mesh run yields rows for every node
    of every co-scheduled fragment WITHOUT leaving the fused data
    plane."""
    frags = _collect_fragments(frag, frozenset(lowerer.fed_fragments))
    volumes: Dict[int, Tuple[float, int, int]] = {}
    for f in frags:
        site = lowerer.fragment_sites.get(f.fragment_id)
        d = host_aux.get(site, {}) if site is not None else {}
        rows = int(np.max(np.asarray(d.get("rows", 0)))) if d else 0
        nbytes = int(np.max(np.asarray(d.get("bytes", 0)))) if d else 0
        volumes[f.fragment_id] = (float(max(rows + nbytes, 1)), rows,
                                  nbytes)
    total = sum(w for w, _, _ in volumes.values()) or 1.0
    for f in frags:
        weight, rows, nbytes = volumes[f.fragment_id]
        share = wall_s * weight / total
        nodes = _plan_nodes(f.root)
        per_node = share / max(len(nodes), 1)
        for n in nodes:
            st = col.register(n)
            st.wall_s += per_node
            st.device_s += per_node
            st.fused = True     # exclusive share, not an inclusive wall
        root_st = col.register(f.root)
        root_st.output_rows += rows
        root_st.output_bytes += nbytes
        root_st.pages += 1


def _run_program(runner, top_fn, staged, struct_key,
                 ladder: Dict[int, int], params: Tuple):
    mesh = runner.mesh
    ladder_snapshot = dict(ladder)
    key = struct_key + (tuple(sorted(ladder_snapshot.items())),)

    def build():
        def per_shard(params, *pages):
            env = _Env(pages, ladder_snapshot, params)
            out = top_fn(env)
            return out, env.aux
        return mesh.shard_map(per_shard, replicated=1)
    # profiled dispatch: a mesh program is the most expensive compile in
    # the engine — its XLA compile wall must land on compile_time_ms,
    # not hide inside the first dispatch. The hoisted literals go in as
    # one replicated operand: another DATE dispatches this executable
    from trino_tpu.exec.jit_cache import profiled_kernel
    prog = profiled_kernel(key, build, params)
    return prog(params, *staged)


def _ladder_bumps(lowerer: MeshLowerer, host_aux: Dict[int, dict]
                  ) -> Dict[int, int]:
    """Read each site's aux scalars and decide capacity doublings. Aux
    leaves are [n]-replicated (psum'd / identical per shard); take max."""
    bumps: Dict[int, int] = {}
    for site, kind in enumerate(lowerer.sites):
        d = host_aux.get(site)
        if d is None:
            continue
        if kind == "a2a" and "overflow" in d:
            if int(np.max(np.asarray(d["overflow"]))) > 0:
                bumps[site] = 2 * int(np.max(np.asarray(d["bucket"])))
        elif kind == "join":
            total = int(np.max(np.asarray(d["total"])))
            cap = int(np.max(np.asarray(d["cap"])))
            if total > cap:
                bumps[site] = _next_pow2(total)
    return bumps
