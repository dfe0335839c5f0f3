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
repeated query shape dispatches a warm executable.

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

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.errors import GENERIC_INTERNAL_ERROR, TrinoError
from trino_tpu.exec.jit_cache import cached_kernel
from trino_tpu.exec.local_planner import _layout, _next_pow2, lower_expr
from trino_tpu.expr.compiler import compile_expression, compile_filter
from trino_tpu.ops import (AggSpec, JoinType, SortKey, Step, hash_aggregate,
                           hash_join, order_by, top_n)
from trino_tpu.ops.aggregate import (COLLECT_AGGREGATES, get_aggregate)
from trino_tpu.page import (Column, Page, count_host_staging,
                            union_dictionaries)
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


class _Env:
    """Per-trace state a lowered closure tree reads: the staged leaf pages
    (positional) and the capacity ladder; closures deposit per-site aux
    scalars (overflow counters, true totals, exchanged rows) keyed by
    static site id — the host reads them back to drive the ladder."""

    def __init__(self, pages: Sequence[Page], ladder: Dict[int, int]):
        self.pages = list(pages)
        self.ladder = ladder
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
    rows = jax.lax.psum(page.num_rows.astype(jnp.int64), AXIS)
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
        self.scans: List[TableScanNode] = []
        self.sites: List[str] = []       # site id -> kind (a2a | join)
        self.key_parts: List = []        # canonical structure key
        self.exchange_sites: List[int] = []
        # fragment id -> the exchange site that carries ITS output
        # (program-level stats apportion the measured wall by each
        # fragment's psum'd exchanged volume read off these sites)
        self.fragment_sites: Dict[int, int] = {}
        self._skew = bool(session.get("skewed_exchange_enabled"))
        self._skew_k = max(1, int(session.get("skew_heavy_key_limit")))
        # MXU join bodies (ops/join_mxu.py): when the optimizer stamped
        # a join `mxu-matmul`, its in-program probe computes BOTH the
        # blocked-indicator-matmul and the searchsorted lookup and
        # selects per shard with a branchless `where` on the traced key
        # span (a lax.cond formulation miscompiled under shard_map
        # fusion — do not reintroduce it). The matmul body composes
        # with the fused all_to_all exchanges (spans are per-shard
        # values a co-partitioned exchange just changed); each mxu site
        # reports whether any shard actually took the matmul result
        # through its aux, feeding the query's mxu_joins/mxu_flops.
        self._mxu_slots = int(session.get("mxu_join_max_slots")) \
            if bool(session.get("mxu_join_enabled")) else None
        self.mxu_sites: List[int] = []   # join sites with an mxu body

    # ------------------------------------------------------------ plumbing

    def _key(self, *parts) -> None:
        self.key_parts.append(tuple(parts))

    def _site(self, kind: str) -> int:
        self.sites.append(kind)
        return len(self.sites) - 1

    def _expr(self, e, layout, types):
        """Lower + bind one expression for in-program evaluation. Literals
        stay baked in (the program key carries them); EXECUTE parameters
        bind from the statement's values."""
        from trino_tpu.expr.hoist import materialize_bound
        return materialize_bound(lower_expr(e, layout, types),
                                 self.exec_params)

    # ------------------------------------------------------------- entry

    def lower_child(self, frag: PlanFragment, remote: RemoteSourceNode
                    ) -> Callable:
        """The co-scheduled unit: child fragment tree + its consuming
        exchange. Returns fn(env) -> per-shard Page (post-exchange)."""
        inner = self.lower_node(frag.root, frag)
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
                    max(1024, _next_pow2(max(1, page.capacity // n)))
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
                out = sort_op(out)
            _exchange_aux(env, site, page, {})
            return out
        return fn

    # ------------------------------------------------------------- nodes

    def lower_node(self, node, frag: PlanFragment
                   ) -> Callable:
        name = type(node).__name__
        method = getattr(self, f"_lower_{name}", None)
        if method is None:
            raise MeshUnsupported(f"no mesh lowering for {name}")
        return method(node, frag)

    def _lower_TableScanNode(self, node: TableScanNode, frag) -> Callable:
        idx = len(self.scans)
        self.scans.append(node)
        self._key("scan", node.catalog, str(node.table),
                  tuple(s.name for s, _ in node.assignments))
        return lambda env: env.pages[idx]

    def _lower_RemoteSourceNode(self, node: RemoteSourceNode, frag
                                ) -> Callable:
        child = next((c for c in frag.children
                      if c.fragment_id == node.fragment_id), None)
        if child is None:
            raise MeshUnsupported(f"missing child {node.fragment_id}")
        inner = self.lower_node(child.root, child)
        return self._lower_exchange(inner, node.kind, node.partition_keys,
                                    node.order_by,
                                    tuple(child.root.outputs),
                                    frag_id=child.fragment_id)

    def _lower_FilterNode(self, node: FilterNode, frag) -> Callable:
        src = self.lower_node(node.source, frag)
        lay, typ = _layout(node.source.outputs)
        pred = self._expr(node.predicate, lay, typ)
        self._key("filter", pred)
        f = compile_filter(pred)
        return lambda env: (lambda p: p.filter(f(p, ())))(src(env))

    def _lower_ProjectNode(self, node: ProjectNode, frag) -> Callable:
        src = self.lower_node(node.source, frag)
        lay, typ = _layout(node.source.outputs)
        exprs = tuple(self._expr(e, lay, typ)
                      for _, e in node.assignments)
        self._key("project", exprs)
        fns = [compile_expression(e) for e in exprs]

        def fn(env: _Env) -> Page:
            page = src(env)
            return Page(tuple(f(page, ()) for f in fns), page.num_rows)
        return fn

    def _lower_LimitNode(self, node: LimitNode, frag) -> Callable:
        if not node.partial:
            raise MeshUnsupported("non-partial LIMIT in sharded fragment")
        src = self.lower_node(node.source, frag)
        self._key("limit", node.count)

        def fn(env: _Env) -> Page:
            page = src(env)
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
        return lambda env: op(src(env))

    def _lower_SortNode(self, node: SortNode, frag) -> Callable:
        src = self.lower_node(node.source, frag)
        lay, _ = _layout(node.source.outputs)
        keys = [SortKey(lay[o.symbol.name], o.ascending, o.nulls_first)
                for o in node.order_by]
        self._key("sort", tuple(keys))
        op = order_by(keys)
        return lambda env: op(src(env))

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
        op = window(part, okeys, specs)
        return lambda env: op(src(env))

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
        src = self.lower_node(node.source, frag)
        if node.step == AggStep.PARTIAL:
            lay, _ = _layout(node.source.outputs)
            keys = tuple(lay[s.name] for s in node.group_by)
            specs = self._agg_specs(node, lay)
            self._key("agg-partial", keys, specs)
            op = hash_aggregate(list(keys), list(specs), Step.PARTIAL)
            return lambda env: op(src(env))
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
            op = hash_aggregate(list(range(nkeys)), list(specs),
                                Step.FINAL, state_channels)
            return lambda env: op(src(env))
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
        op = hash_aggregate(list(keys), list(specs), Step.SINGLE)
        return lambda env: op(src(env))

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
            return lambda env: (lambda p: Page(
                tuple(p.columns[c] for c in order), p.num_rows))(inner(env))
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
        mxu = self._mxu_slots \
            if (getattr(node, "join_strategy", None) == "mxu-matmul"
                and len(node.criteria) == 1) else None
        if mxu is not None:
            self.mxu_sites.append(site)
        self._key("join", probe_keys, build_keys, join_kind, post_pred,
                  probe_keep, build_keep, mxu)

        def fn(env: _Env) -> Page:
            if psite is None:
                probe = probe_fn(env)
                build = build_fn(env)
            else:
                probe, build = self._apply_skewed_pair(
                    env, probe_fn, build_fn, ppre_keys, bpre_keys,
                    psite, bsite)
            probe = _align_key_dictionaries(probe, build, probe_keys,
                                            build_keys)
            cap = env.ladder.get(site) or probe.capacity
            op = hash_join(list(probe_keys), list(build_keys), join_kind,
                           output_capacity=cap, prepared=False,
                           mxu_slots=mxu,
                           probe_out=probe_keep, build_out=build_keep)
            out, total = op(probe, build)
            if post_filter is not None:
                out = out.filter(post_filter(out, ()))
            # per-shard: the host takes the max over shards
            # (_ladder_bumps); an in-program pmax over int64 is a
            # collective the TPU compiler does not lower (only a 64-bit
            # SUM all-reduce is)
            aux = {"total": total.astype(jnp.int64),
                   "cap": jnp.int32(cap)}
            if mxu is not None:
                aux.update(_mxu_aux(probe, build, build_keys[0], mxu))
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
        probe_fn = self.lower_node(lchild.root, lchild)
        build_fn = self.lower_node(rchild.root, rchild)
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
            max(1024, _next_pow2(max(1, probe_pre.capacity // n)))
        bbucket = env.ladder.get(bsite) or \
            max(1024, 2 * _next_pow2(max(1, build_pre.capacity // n)))
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
        mxu = self._mxu_slots \
            if (getattr(node, "join_strategy", None) == "mxu-matmul"
                and len(node.source_keys) == 1) else None
        if mxu is not None:
            self.mxu_sites.append(site)
        self._key("semijoin", probe_keys, build_keys, node.null_aware,
                  mxu)

        def fn(env: _Env) -> Page:
            probe = probe_fn(env)
            build = build_fn(env)
            probe = _align_key_dictionaries(probe, build, probe_keys,
                                            build_keys)
            cap = env.ladder.get(site) or probe.capacity
            op = hash_join(list(probe_keys), list(build_keys),
                           JoinType.MARK, output_capacity=cap,
                           prepared=False, mxu_slots=mxu,
                           null_aware=node.null_aware)
            out, total = op(probe, build)
            # per-shard total, as in the join lowering above
            aux = {"total": total.astype(jnp.int64),
                   "cap": jnp.int32(cap)}
            if mxu is not None:
                aux.update(_mxu_aux(probe, build, build_keys[0], mxu))
            env.aux[site] = aux
            return out
        return fn

    def _lower_AssignUniqueIdNode(self, node, frag) -> Callable:
        src = self.lower_node(node.source, frag)
        self._key("assign-unique-id")

        def fn(env: _Env) -> Page:
            page = src(env)
            base = jax.lax.axis_index(AXIS).astype(jnp.int64) << 44
            idx = jnp.arange(page.capacity, dtype=jnp.int64) + base
            col = Column(idx, None, T.BIGINT, None)
            return Page(tuple(page.columns) + (col,), page.num_rows)
        return fn


def _mxu_aux(probe: Page, build: Page, build_key: int,
             mxu_slots: int) -> dict:
    """Per-shard truth for the mxu counters: whether this shard's key
    span fits the matmul table (the same predicate hash_join's inline
    body selects on, incl. the static f32-exactness gate) and the MAC
    count its lookup issued — psum'd so every shard carries the global
    counts."""
    from trino_tpu.ops.join_mxu import key_bounds, lookup_flops
    if build.capacity >= (1 << 24):     # hash_join's static mxu gate
        zero = jnp.int64(0)
        return {"mxu": jnp.int32(0), "mxu_flops": zero}
    kmin, kmax = key_bounds(build_key)(build)
    ok = (kmax >= kmin) & ((kmax - kmin) < jnp.uint64(mxu_slots))
    flops = jnp.where(ok, lookup_flops(probe.capacity, mxu_slots, 2),
                      0).astype(jnp.int64)
    return {"mxu": jax.lax.psum(ok.astype(jnp.int32), AXIS),
            "mxu_flops": jax.lax.psum(flops, AXIS)}


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


def _stage_scan(runner, node: TableScanNode) -> Tuple[List[Page], int]:
    """Read one leaf scan as n per-shard pages (split round-robin, the
    SourcePartitionedScheduler assignment), each merged to one page; the
    caller normalizes + stacks them into a workers-sharded global Page.

    Device-resident table cache: when the scan's columns are already
    promoted into HBM, the per-shard pages are ROW-RANGE SLICES of the
    resident arrays — the shard placement that follows is a device-to-
    device move, so a warm repeated mesh scan stages ZERO host->device
    bytes (scan_staging_bytes, the mesh-side counter proof). A cold
    mesh scan both stages from the connector (counted) and, once the
    working set is hot enough, promotes from its own normalized pages."""
    import dataclasses as _dc

    from trino_tpu.exec.distributed import (_empty_like, _normalize_pages,
                                            split_scan_capacity)
    from trino_tpu.exec.memory import page_bytes
    from trino_tpu.predicate import TupleDomain
    conn = runner.metadata.connector(node.catalog)
    columns = [c for _, c in node.assignments]
    names = [c.name for c in columns]
    n = runner.mesh.n
    col = runner._collector
    st = node.table.name
    tkey = (node.catalog, st.schema, st.table)
    tcache = None if node.catalog == "system" \
        else runner._active_table_cache()
    tgen = None if tcache is None else tcache.generation()
    if tcache is not None:
        entry = tcache.lookup(tkey, names)
        if entry is not None:
            if col is not None:
                col.table_cache_hit()
            from trino_tpu.exec.table_cache import build_shard_pages
            per_shard = build_shard_pages(entry, names, n)
            ref = next((p for p in per_shard if p is not None), None)
            if ref is None:
                raise MeshUnsupported(f"empty table {node.table}")
            per_shard = [_empty_like(ref) if p is None else p
                         for p in per_shard]
            return _normalize_pages(per_shard), ref.capacity
        if col is not None:
            col.table_cache_miss()
    handle = node.table
    prunes = getattr(conn.metadata, "supports_zone_maps", False)
    if prunes and not bool(
            runner.session.get("lake_zone_maps_enabled")):
        handle = _dc.replace(handle, constraint=TupleDomain.all())
    splits = conn.split_manager.get_splits(handle, target_splits=n)
    cap = split_scan_capacity(runner.session, conn, node, splits)
    per_shard: List[Optional[Page]] = []
    try:
        for shard in range(n):
            mine = [s for s in splits if s.part % n == shard]
            pages: List[Page] = []
            for split in mine:
                for page, moved in count_host_staging(
                        conn.page_source.pages(split, columns, cap)):
                    if col is not None:
                        col.add_scan_staging(page_bytes(page), moved)
                    pages.append(page)
            if not pages:
                per_shard.append(None)
            elif len(pages) == 1:
                per_shard.append(pages[0])
            else:
                from trino_tpu.page import device_concat
                key = ("mesh-sconcat", tuple(p.capacity for p in pages),
                       pages[0].num_columns)
                op = cached_kernel(key,
                                   lambda: lambda *ps: device_concat(ps))
                per_shard.append(op(*pages))
    finally:
        take = getattr(conn, "take_scan_stats", None)
        if take is not None:
            d = take() or {}
            if col is not None and d:
                col.add_pruned(d.get("files_pruned", 0),
                               d.get("row_groups_pruned", 0))
    ref = next((p for p in per_shard if p is not None), None)
    if ref is None:
        raise MeshUnsupported(f"empty table {node.table}")
    per_shard = [_empty_like(ref) if p is None else p for p in per_shard]
    normalized = _normalize_pages(per_shard)
    if tcache is not None and node.table.limit is None \
            and (not prunes or handle.constraint.is_all()):
        # hot-set promotion from the just-normalized device pages
        # (shared dictionaries by construction) — the NEXT mesh scan of
        # this table stages zero host bytes
        if tcache.note_scan(tkey, names) >= max(1, int(
                runner.session.get("table_cache_min_scans"))) \
                and tcache.should_promote(tkey, names):
            counts = [int(c) for c in jax.device_get(
                [p.num_rows for p in normalized])]
            tcache.promote_from_pages(
                tkey, list(zip(names, columns)), normalized, counts,
                collector=col, gen=tgen)
    return normalized, cap


def run_co_scheduled(runner, frag: PlanFragment,
                     remote: RemoteSourceNode) -> List[Optional[Page]]:
    """Execute `frag` (and its whole child tree) plus the consuming
    exchange as ONE jitted shard_map program over the runner's mesh.
    Returns per-shard post-exchange pages for the parent fragment, or
    raises MeshUnsupported for the dispatch-loop fallback."""
    mesh = runner.mesh
    lowerer = MeshLowerer(runner.session, runner.metadata, mesh.n,
                          runner._exec_params)
    top_fn = lowerer.lower_child(frag, remote)   # may raise MeshUnsupported

    runner._check_deadline()
    staged: List[Page] = []
    staged_bytes: List[List[int]] = []
    from trino_tpu.exec.memory import live_page_bytes, page_bytes
    for scan in lowerer.scans:
        pages, _cap = _stage_scan(runner, scan)
        staged_bytes.append([page_bytes(p) for p in pages])
        staged.append(mesh.shard_pages(pages))

    ledger = runner._memory
    reserved: List[Tuple[int, int]] = []
    if ledger is not None:
        for per_shard in staged_bytes:
            for shard, nbytes in enumerate(per_shard):
                ledger.reserve(nbytes, "mesh-stage", device=shard)
                reserved.append((nbytes, shard))

    struct_key = ("mesh-prog", tuple(lowerer.key_parts), mesh.n)
    col = runner._collector
    stats_on = col is not None and col.operator_level
    program_wall = 0.0
    try:
        ladder: Dict[int, int] = {}
        for _round in range(_MAX_LADDER_ROUNDS):
            runner._check_deadline()
            pre_device = col.device_time_s if stats_on else 0.0
            out_global, aux = _run_program(
                runner, lowerer, top_fn, staged, struct_key, ladder)
            if stats_on:
                # the round's device wall: the program is ONE XLA call,
                # and the jit cache's fenced dispatch timed it (clock
                # stopped at block_until_ready — BEFORE the aux host
                # transfer and the ladder's NumPy analysis, compile wall
                # out) and added it to the query's device time. The
                # CONVERGED round's wall is what the operators share.
                round_wall = col.device_time_s - pre_device
            host_aux = jax.device_get(aux)
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

    from trino_tpu.exec.distributed import _unstack_page
    per_shard = _unstack_page(out_global, mesh.n)
    # per-chip peak accounting for the exchange outputs the parent will
    # consume (reserve+free: the gauge is the peak, the pages themselves
    # are owned by XLA until the parent materializes results)
    if ledger is not None:
        for shard, p in enumerate(per_shard):
            if p is not None:
                nbytes = page_bytes(p)
                ledger.reserve(nbytes, "mesh-exchange", device=shard)
                ledger.free(nbytes, "mesh-exchange", device=shard)

    if col is not None:
        col.mesh_devices = mesh.n
        # count the joins whose matmul result was ACTUALLY selected on
        # at least one shard (the per-site psum'd span-ok aux), with the
        # summed cost-model MACs those shards issued — 'what ran', not
        # 'what lowered'
        mxu_ran = 0
        for site in lowerer.mxu_sites:
            d = host_aux.get(site, {})
            if int(np.max(np.asarray(d.get("mxu", 0)))) > 0:
                mxu_ran += 1
                col.add_mxu_flops(
                    int(np.max(np.asarray(d.get("mxu_flops", 0)))))
        if mxu_ran:
            col.mxu_join(mxu_ran)
        for site in lowerer.exchange_sites:
            d = host_aux.get(site, {})
            col.add_exchange(
                "fused",
                rows=int(np.max(np.asarray(d.get("rows", 0)))),
                nbytes=int(np.max(np.asarray(d.get("bytes", 0)))))
    if stats_on:
        _record_program_stats(col, lowerer, frag, program_wall, host_aux)
    return per_shard


def _collect_fragments(frag: PlanFragment) -> List[PlanFragment]:
    out = [frag]
    for child in frag.children:
        out.extend(_collect_fragments(child))
    return out


def _plan_nodes(node) -> List:
    out = [node]
    for s in node.sources:
        out.extend(_plan_nodes(s))
    return out


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
    frags = _collect_fragments(frag)
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


def _run_program(runner, lowerer: MeshLowerer, top_fn, staged,
                 struct_key, ladder: Dict[int, int]):
    mesh = runner.mesh
    ladder_snapshot = dict(ladder)
    key = struct_key + (tuple(sorted(ladder_snapshot.items())),)

    def build():
        def per_shard(*pages):
            env = _Env(pages, ladder_snapshot)
            out = top_fn(env)
            return out, env.aux
        return mesh.shard_map(per_shard)
    # profiled dispatch: a mesh program is the most expensive compile in
    # the engine — its XLA compile wall must land on compile_time_ms,
    # not hide inside the first dispatch
    from trino_tpu.exec.jit_cache import profiled_kernel
    prog = profiled_kernel(key, build)
    return prog(*staged)


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
