"""Local execution: lower a logical plan to streaming page pipelines.

Reference parity: sql/planner/LocalExecutionPlanner.java:420 — each plan node
maps to an operator implementation over Pages (visitTableScan:1733,
visitFilter/visitProject via ScanFilterAndProject:1606, visitAggregation:1534,
visitJoin:2109, visitTopN, visitSort, visitLimit, visitSemiJoin, ...).

Execution model (Driver.java replacement): a node executes to an iterator of
fixed-capacity Pages plus a symbol layout. Device work per page runs under
jit — traces cache on (capacity, dtypes), so steady-state streaming is one
compiled XLA call per page per pipeline stage. Blocking operators (agg, sort,
join build) consume their input eagerly, as their Java counterparts do across
addInput/finish.

Dynamic row counts under static shapes (SURVEY §7 hard part 1): operators
carry a true-total scalar; when an output overflows its static capacity the
executor doubles the capacity bucket and re-runs (hash_join contract).
"""

from __future__ import annotations

import collections
import dataclasses
import threading
from typing import (Callable, Dict, Iterator, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.connector.spi import Split
from trino_tpu.errors import GENERIC_INTERNAL_ERROR, TrinoError
from trino_tpu.exec.jit_cache import (cached_kernel, get_observer,
                                      host_read, key_tag, observed_activity,
                                      profiled_kernel, program_name, pulled)
from trino_tpu.expr.compiler import compile_expression, compile_filter
from trino_tpu.expr.ir import (Call, InputRef, Literal, RowExpression,
                               SpecialForm, SpecialKind, SymbolRef)
from trino_tpu.metadata import Metadata, Session
from trino_tpu.ops import (AggSpec, JoinType, SortKey, Step, hash_aggregate,
                           hash_join, order_by, prepare_build, top_n,
                           top_n_masked)
from trino_tpu.ops.join import set_semi_join, unique_inner_probe
from trino_tpu.page import (Column, Page, SplitColumn, concat_pages,
                            count_host_staging, defer_compaction,
                            host_staged_bytes, in_chunks, op_scope)
from trino_tpu.planner.nodes import (
    AggregationNode, DistinctLimitNode, EnforceSingleRowNode,
    ExchangeNode, FilterNode, GroupIdNode, JoinClause, JoinKind, JoinNode,
    LimitNode, OffsetNode, OutputNode, PlanNode, ProjectNode, SemiJoinNode,
    SortNode, Symbol, TableScanNode, TopNNode, UnionNode, ValuesNode,
    WindowNode, TableWriterNode)


class ExecutionError(TrinoError):
    """Operator-lowering/runtime defect: internal, not retryable (the
    same plan re-fails identically)."""

    CODE = GENERIC_INTERNAL_ERROR


def lower_expr(e: RowExpression, layout: Dict[str, int],
               types: Dict[str, T.Type]) -> RowExpression:
    """SymbolRef -> InputRef against a page layout (the compiled-PageProcessor
    channel mapping step)."""
    if isinstance(e, SymbolRef):
        if e.name not in layout:
            raise ExecutionError(f"symbol {e.name} not in layout")
        return InputRef(layout[e.name], types[e.name])
    if isinstance(e, Call):
        return Call(e.name, tuple(lower_expr(a, layout, types)
                                  for a in e.args), e.type)
    if isinstance(e, SpecialForm):
        return SpecialForm(e.kind, tuple(lower_expr(a, layout, types)
                                         for a in e.args), e.type)
    return e


def _layout(symbols: Sequence[Symbol]) -> Tuple[Dict[str, int],
                                                Dict[str, T.Type]]:
    lay = {s.name: i for i, s in enumerate(symbols)}
    typ = {s.name: s.type for s in symbols}
    return lay, typ


def _next_pow2(n: int) -> int:
    out = 1024
    while out < n:
        out *= 2
    return out


class WholeColumns(NamedTuple):
    """One split of a scan as whole resident columns (`PageStream.whole`):
    `rows` live rows in buffers that hold at least their pages of
    `capacity` lanes. `staged` / `moved`: the bytes the scan counts as
    pulled from its connector, and as moved host -> device for it, if
    these columns are what it reads (a table-cache entry counts none)."""

    columns: tuple
    rows: int
    capacity: int
    staged: int = 0
    moved: int = 0


@dataclasses.dataclass
class PageStream:
    """Stream of pages + a lazy chain of per-page device transforms.

    WorkProcessor-style (operator/WorkProcessor.java:31): streaming operators
    (filter/project/column-select) don't dispatch device work themselves —
    they append (cache_key, kernel_builder, params) entries to `pending`.
    Consumers drain via iter_pages(), which compiles ONE composed kernel for
    the whole chain (cached), so a scan->filter->project pipeline is a single
    XLA program per page, and blocking operators can fuse the chain into
    their own kernel (ScanFilterAndProjectOperator's fusion, compile-once).

    `params` per entry is the op's hoisted-literal tuple (expr/hoist.py):
    keys carry the literal-free canonical expression, and the values flow
    into the composed kernel as traced scalar operands — so every literal
    variant of a chain shape shares one XLA executable. Builders therefore
    return fn(page, params), with params=() for literal-free ops.

    Operator attribution (round 13): under operator-level stats
    collection each entry may carry a FOURTH element — the owning plan
    node's OperatorStats slot. The slot never enters the chain cache key
    (canonical keys stay literal- and query-free), and it never splits
    the chain: compose_chain times the fused dispatch once and
    apportions the measured wall across the tagged entries by XLA cost
    analysis (obs/profiler.py). Entries without a slot are plain
    3-tuples, so the untagged fast path is byte-identical to before.
    """

    pages: Iterator[Page]
    symbols: Tuple[Symbol, ...]
    pending: Tuple[tuple, ...] = ()
    # a scan of resident columns offers them whole as well: `whole()` is
    # None or a `WholeColumns` a split — the same rows as `pages`, each
    # column ONE device buffer — for a consumer that walks the pages
    # inside its program (`compose_walk`). Only lane-wise steps
    # (`_LANE_WISE_STEPS`) carry it on; whoever takes it leaves `pages`
    # unpulled
    whole: Optional[Callable[[], Optional[list]]] = None

    def with_op(self, key, builder, params=()) -> "PageStream":
        return PageStream(self.pages, self.symbols,
                          self.pending + ((key, builder, tuple(params)),))

    def iter_pages(self) -> Iterator[Page]:
        fn = compose_chain(self.pending)
        if fn is None:
            yield from self.pages
        else:
            for p in self.pages:
                yield fn(p)


def _composite(tag: str, keys, outer: bool = False) -> str:
    """A join program's tag: `-outer` after it where the join preserves a
    side (LEFT, FULL; a RIGHT join runs as a LEFT one) — its unmatched probe
    rows come out null-extended (ops/join's scope `join__outer_fill`) —,
    then `-composite` where the key has more than one column: such a key is
    mix-hashed to 64 bits (ops/join._key_u64), so the lookup is `search`
    and every candidate is verified. The trace gives those programs names
    of their own; an INNER join on one column keeps the bare tag."""
    if outer:
        tag = f"{tag}-outer"
    return f"{tag}-composite" if len(keys) > 1 else tag


def chain_keys(pending) -> Tuple:
    return tuple(e[0] for e in pending)


def chain_params(pending) -> Tuple:
    """Per-op runtime literal tuples, positionally aligned with
    chain_keys — the traced argument the composed kernel receives."""
    return tuple(tuple(e[2]) for e in pending)


# a filter step by either of its tags: `agg-having` is the filter over an
# aggregation's output, named for the family whose groups it judges
_FILTER_STEPS = frozenset({"filter", "agg-having"})
# chain steps that touch each lane on its own (no row moves, no row is
# read by position): the only steps a deferred filter may sit among
_LANE_WISE_STEPS = _FILTER_STEPS | {"project", "select"}


def chain_defers_compaction(key) -> bool:
    """Whether the chain `key` names runs its filters deferred (a selection
    mask, no compaction): its tail is the partial hash aggregate, which
    takes liveness from `Page.row_mask()` alone, and every step before it
    is lane-wise. Read off the cache key, so the two modes can never share
    a program. Everything else compacts: tail-less chains (their pages
    leave the program), `agg-bypass` (one state row per input row under
    `num_rows`), TopN, and any step kind not listed."""
    return len(key) > 1 and key_tag(key[-1]) == "agg-partial" and all(
        key_tag(k) in _LANE_WISE_STEPS for k in key[1:-1])


def chain_steps(key, pending, tail_builder=None):
    """(step functions, tail function or None) exactly as the chain
    program `key` runs them, each under its scope: the one place that
    decides a chain's mode (the profiler costs a chain through it).

    In deferred mode the chain owns the selection: a filter ANDs its mask
    into it (`Page.filter` under `defer_compaction`), and a step that
    builds a fresh `Page(cols, page.num_rows)` (project, select) gets the
    carried one re-attached, so no step can lose it."""
    defer = chain_defers_compaction(key)
    # one scope per step and the tail's: the trace gives each fused
    # op's device time to the operator it came from (HLO metadata
    # only: the executable is the same)
    scopes = [program_name((k,)) for k in key[1:]]

    def step_of(f, scope):
        def step(page, g):
            with defer_compaction(defer), op_scope(scope):
                out = f(page, g)
            if defer and out.selection is None \
                    and page.selection is not None:
                assert out.capacity == page.capacity, key
                out = out.with_selection(page.selection)
            return out
        return step

    def tail_of(f, scope):
        def tail(page):
            with op_scope(scope):
                return f(page)
        return tail
    steps = [step_of(e[1](), scope) for e, scope in zip(pending, scopes)]
    tail = None if tail_builder is None else \
        tail_of(tail_builder(), scopes[-1])
    return steps, tail


def _chain_program(key, pending, tail_builder):
    """The builder of the chain program `key`: `run(page, groups)`, every
    step and then the tail over one page."""
    def build():
        steps, tail = chain_steps(key, pending, tail_builder)

        def run(page, groups):
            for step, g in zip(steps, groups):
                page = step(page, g)
            if tail is not None:
                page = tail(page)
            # trace time: a page with a selection never leaves its program
            assert getattr(page, "selection", None) is None, key
            return page
        return run
    return build


def compose_chain(pending, tail_key=None, tail_builder=None,
                  tail_slot=None):
    """One cached jitted kernel running every pending transform (+ optional
    tail op, e.g. a partial aggregation) in a single device program. The
    cache key holds only canonical (literal-free) op keys; hoisted literal
    values are passed per call, so `fn(page)` for a new literal variant of
    a warm chain dispatches the existing executable.

    A chain that ends in the partial hash aggregate over lane-wise steps
    runs its filters deferred (`chain_defers_compaction`); each dispatch
    of a chain with a filter step counts on the query's collector as
    `compactions_deferred` or `compactions_run`.

    Dispatch goes through the jit cache's profiled path, so every XLA
    compile a chain triggers is a timed, query-attributed event
    (compile_time_ms). When any entry carries an OperatorStats slot
    (operator-level collection — `tail_slot` is the blocking consumer's
    slot for fused tails), each dispatch is additionally fenced at CHAIN
    granularity and the measured device wall is apportioned across the
    chain's operators by XLA cost analysis: stats collection observes
    the SAME executables the plain query runs — no chain splitting."""
    if not pending and tail_builder is None:
        return None
    key = ("chain",) + chain_keys(pending) + \
        ((tail_key,) if tail_key is not None else ())
    param_groups = chain_params(pending)
    build = _chain_program(key, pending, tail_builder)
    kernel = profiled_kernel(key, build, params=param_groups)
    resolve = _like_tables(key, build, param_groups)
    if resolve is not None:
        unresolved = kernel

        def kernel(page, groups):
            return unresolved(page, resolve(page))
    if any(key_tag(k) in _FILTER_STEPS for k in key[1:]):
        deferred = chain_defers_compaction(key)
        dispatch = kernel

        def kernel(page, groups):
            count = getattr(get_observer(), "count_compaction", None)
            if count is not None:
                count(deferred)
            return dispatch(page, groups)

    slots = tuple(e[3] if len(e) > 3 else None for e in pending)
    if all(s is None for s in slots) and tail_slot is None:
        def call(page):
            return kernel(page, param_groups)
        return call
    return _attributed_chain_call(kernel, key, pending, param_groups,
                                  slots, tail_builder, tail_slot, resolve)


# lanes one launch of a walking chain covers at most. A launch cannot be
# preempted — `SliceScheduler.capacity_cap` bounds a scan page for that
# reason — and a lane-wise chain into a direct reduce is 0.24 ms a
# 1 048 576-lane page on a v5e (PERF.md section 6, PR 43): 2^26 lanes are
# 15-25 ms against `slice_target_ms` 250. A larger table takes several
# launches, the cooperative boundary between them
_WALK_LANES = 1 << 26


@dataclasses.dataclass(frozen=True)
class ColumnSpan:
    """Pages [first, first + pages) of a scan's resident columns: what one
    launch of a walking chain covers. `columns` are the WHOLE buffers and
    `rows` their live rows; a page of the span that lies past them is
    walked and counts nothing."""

    columns: Tuple[Column, ...]
    rows: int
    capacity: int       # lanes of a page
    first: int
    pages: int

    @property
    def live_pages(self) -> int:
        return max(0, min(self.pages,
                          -(-self.rows // self.capacity) - self.first))

    @property
    def num_rows(self) -> int:
        """Live rows of the span: what the slice budget counts."""
        return max(0, min(self.rows - self.first * self.capacity,
                          self.pages * self.capacity))


def column_spans(columns, rows: int, capacity: int) -> Iterator[ColumnSpan]:
    """The launches that walk `rows` rows of whole columns a page of
    `capacity` lanes at a time: equal spans of at most `_WALK_LANES`
    lanes, so one executable serves them all."""
    pages = -(-rows // capacity)
    span = min(pages, max(_WALK_LANES // capacity, 1))
    for first in range(0, pages, span):
        yield ColumnSpan(tuple(columns), rows, capacity, first, span)


# (walking chain key, the columns' avals) -> whether the chain's partial
# aggregate keeps few states there: what one abstract trace found
_WALKS: "collections.OrderedDict" = collections.OrderedDict()


def compose_walk(pending, tail_key, tail_builder, sample: ColumnSpan):
    """`compose_chain`'s program walking its pages INSIDE one launch, or
    None where this chain must take a page a launch.

    A chain walks when it runs deferred into the partial aggregate
    (`chain_defers_compaction`: every step lane-wise) and that aggregate
    keeps few states a page — global, or direct-address slots — which one
    abstract trace of the steps over a page of `sample`'s columns tells
    (`jax.eval_shape`, kept per chain and column shapes; never a table's
    or a query's name). Q18's inner GROUP BY sorts, holds a state a lane
    and is flushed between pages: it does not walk.

    `call(span)` hands the program each column as ONE buffer (a
    `SplitColumn` as its two); it takes
    `span.pages` pages of `span.capacity` lanes from page `span.first`
    (`page.in_chunks`: the same steps under the same scopes, a page's
    arithmetic unchanged, literals and LIKE tables passed once) and
    returns the pages' partial states as one small page, which the
    caller's buffer, merge and FINAL take as they take a page's. One key
    — one executable, one program name, the per-page chain's — for every
    span of these shapes. Counts `chain_walks` / `chain_walk_pages` on
    the query's collector, and the pages as `compactions_deferred`."""
    key = ("chain",) + chain_keys(pending) + (
        tail_key + (("walk", sample.capacity, sample.pages),),)
    if not chain_defers_compaction(key):
        return None
    param_groups = chain_params(pending)
    per_page = _chain_program(key, pending, tail_builder)
    resolve = _like_tables(key, per_page, param_groups)
    capacity, pages = sample.capacity, sample.pages

    def page_of(columns):
        """A page of the walk as the steps see it, by shape."""
        def lanes(x, dtype=None):
            return jax.ShapeDtypeStruct((capacity,) + x.shape[1:],
                                        dtype or x.dtype)
        return Page(tuple(
            c.like(lanes(c.low, c.dtype)).with_valid(
                None if c.valid is None else lanes(c.valid))
            if isinstance(c, SplitColumn)
            else jax.tree_util.tree_map(lanes, c) for c in columns),
            jax.ShapeDtypeStruct((), jnp.int32))

    def groups_for(columns):
        return param_groups if resolve is None \
            else resolve(page_of(columns))

    at = (key, jax.tree_util.tree_structure(sample.columns)) + tuple(
        (x.dtype.str, x.shape[1:])
        for x in jax.tree_util.tree_leaves(sample.columns))
    with _LIKE_DICTIONARIES_LOCK:
        few = _WALKS.get(at)
    if few is None:
        from trino_tpu.ops.aggregate import partial_states_are_few
        steps, _ = chain_steps(key, pending)
        groups = groups_for(sample.columns)

        def fed(page):
            for step, g in zip(steps, groups):
                page = step(page, g)
            return page
        _, key_channels, specs = tail_key[:3]
        few = partial_states_are_few(
            jax.eval_shape(fed, page_of(sample.columns)), key_channels,
            specs)
        with _LIKE_DICTIONARIES_LOCK:
            while len(_WALKS) >= _LIKE_DICTIONARIES_KEPT:
                _WALKS.popitem(last=False)
            _WALKS[at] = few
    if not few:
        return None

    def build():
        run = per_page()

        def walk(columns, first, rows, groups):
            def a_page(page):
                # a split column's words joined, a page of them
                return run(Page(tuple(
                    c.column() if isinstance(c, SplitColumn) else c
                    for c in page.columns), page.num_rows), groups)
            return in_chunks(Page(columns, rows), a_page, capacity,
                             (first, pages))
        return walk
    kernel = profiled_kernel(key, build, params=param_groups)
    filters = any(key_tag(k) in _FILTER_STEPS for k in key[1:])

    def call(span: ColumnSpan) -> Page:
        assert (span.capacity, span.pages) == (capacity, pages), key
        count = getattr(get_observer(), "count_walk", None)
        if count is not None:
            count(span.live_pages, filters)
        return kernel(span.columns, np.int32(span.first),
                      np.int32(span.rows), groups_for(span.columns))
    return call


# (chain key, page structure) -> the dictionary behind each LikeOperand of
# the chain, in the operands' order: what one abstract trace found
_LIKE_DICTIONARIES: "collections.OrderedDict" = collections.OrderedDict()
_LIKE_DICTIONARIES_KEPT = 512
_LIKE_DICTIONARIES_LOCK = threading.Lock()


def _like_tables(key, build, param_groups):
    """None for a chain without a `LikeOperand` among its parameters
    (expr/hoist.py), else `resolve(page)`: the chain's parameter groups
    with every operand replaced by its boolean table over the dictionary
    the column has in THIS page's program — built on the host, once per
    (dictionary, pattern) for the `compose_chain` that asked, so once a
    request; the activity `like_table`, the counter `like_tables_built`.

    Which dictionary: the column a LIKE reads may be any expression at
    any step of the chain, so its dictionary is known only to a trace.
    One abstract trace (`jax.eval_shape`: nothing compiles, nothing runs)
    per chain key and page structure — the structure holds every
    dictionary, as jit's own cache key does — tells, and is kept."""
    from trino_tpu.expr.hoist import LikeOperand, finding_dictionaries
    slots = [(gi, pi) for gi, group in enumerate(param_groups)
             for pi, p in enumerate(group) if isinstance(p, LikeOperand)]
    if not slots:
        return None
    tables: dict = {}

    def dictionaries(page):
        at = (key, jax.tree_util.tree_structure(page))
        with _LIKE_DICTIONARIES_LOCK:
            found = _LIKE_DICTIONARIES.get(at)
        if found is None:
            with finding_dictionaries() as by_operand:
                jax.eval_shape(lambda p: build()(p, param_groups), page)
            found = tuple(by_operand[id(param_groups[gi][pi])]
                          for gi, pi in slots)
            with _LIKE_DICTIONARIES_LOCK:   # the server's pool shares it
                while len(_LIKE_DICTIONARIES) >= _LIKE_DICTIONARIES_KEPT:
                    _LIKE_DICTIONARIES.popitem(last=False)
                _LIKE_DICTIONARIES[at] = found
        return found

    def resolve(page):
        groups = [list(g) for g in param_groups]
        for (gi, pi), d in zip(slots, dictionaries(page)):
            table = tables.get((gi, pi, d))
            if table is None:
                observer = get_observer()
                with observed_activity("like_table"):
                    table = tables[gi, pi, d] = param_groups[gi][pi].table(d)
                if hasattr(observer, "like_tables_built"):
                    observer.like_tables_built += 1
            groups[gi][pi] = table
        return tuple(tuple(g) for g in groups)
    return resolve


class DeviceShareSlot:
    """Entry tag that attributes ONLY the device share to a slot — for
    operators whose boundary wrapper already measures inclusive wall and
    counts output rows (masked TopN: its kernel rides the chain, but its
    node's output stream is separately wrapped — full tagging would
    double-count wall and rows on one slot)."""

    def __init__(self, st):
        self.st = st


def _attributed_chain_call(kernel, key, pending, param_groups, slots,
                           tail_builder, tail_slot, resolve=None):
    """The operator-attribution dispatch wrapper: take the chain
    dispatch's fenced device wall (the jit cache times every dispatch of
    a fenced query, compile wall excluded — `jit_cache._timed`) and split
    it across the tagged operators by the profiler's cost weights.
    Fused chain operators jointly report the chain's EXIT
    rows/pages/bytes (they are one kernel — intermediate
    row counts are not observable without splitting the program, which
    is exactly what this path exists to avoid). Cost weights resolve
    ONCE per stream from the first page (they are ratios of a static
    cost model — per-page re-derivation would just repeat the pytree
    walk the dispatch already paid)."""
    import time as _time

    from trino_tpu.exec import jit_cache
    from trino_tpu.exec.memory import live_page_bytes
    from trino_tpu.obs import profiler

    weights_box: list = []

    def call(page):
        observer = jit_cache.get_observer()
        if getattr(observer, "fenced", False):
            before = observer.device_time_s
            out = kernel(page, param_groups)
            wall = observer.device_time_s - before
        else:       # no collector on this thread: fence here, count nowhere
            t0 = _time.perf_counter()
            out = kernel(page, param_groups)
            jax.block_until_ready(out)
            wall = _time.perf_counter() - t0
        if not weights_box:
            weights_box.append(profiler.chain_weights(
                key, lambda: chain_steps(key, pending, tail_builder),
                page, param_groups if resolve is None else resolve(page)))
        shares = profiler.apportion(wall, weights_box[0])
        count_exit = isinstance(out, Page)
        n = int(host_read(out.num_rows, "chain_exit_rows")) \
            if count_exit else 0
        nbytes = live_page_bytes(out, n) if count_exit else 0
        for st, share in zip(slots, shares):
            if isinstance(st, DeviceShareSlot):
                st.st.device_s += share     # wall/rows owned by wrapper
            elif st is not None:
                st.wall_s += share
                st.device_s += share
                st.fused = True
                if count_exit:
                    st.output_rows += n
                    st.pages += 1
                    st.output_bytes += nbytes
        if tail_slot is not None and tail_builder is not None:
            tail_slot.device_s += shares[-1]
        return out
    return call


class LocalExecutionPlanner:
    """Single-process executor over one device (LocalQueryRunner's engine)."""

    def __init__(self, metadata: Metadata, session: Session):
        self.metadata = metadata
        self.session = session
        self.page_capacity = int(session.get("page_capacity"))
        # parameterized kernel compilation (expr/hoist.py): on by default;
        # `SET SESSION hoist_literals = false` pins a misbehaving shape
        # back to per-literal compilation for debugging
        self._hoist_on = bool(session.get("hoist_literals"))
        # the query's QueryStatsCollector (obs/stats.py), installed by the
        # owning runner; operator-level instrumentation wraps node
        # boundaries only when collector.operator_level is on (it forces
        # fused chains apart — see obs/stats.py module docstring)
        self.collector = None
        from trino_tpu.exec.memory import QueryMemoryContext
        self.memory = QueryMemoryContext(
            int(session.get("query_max_memory")))
        # which mesh device this executor's reservations live on (None =
        # single-device execution): shard executors set their shard index
        # so the node pool's per-chip gauges attribute HBM to the chip
        # that actually holds it
        self.mem_device: Optional[int] = None
        # fault-tolerance wiring (exec/faults.py + exec/deadline.py),
        # installed by the owning runner; None = no chaos / no limits
        self.faults = None
        self.deadline = None
        # serving-tier scan cache (trino_tpu/serve/caches.ScanCache),
        # installed by the owning runner when scan_cache_enabled: raw
        # staged pages are reusable by ANY query over the same columns
        # (filters/projections chain downstream per query)
        self.scan_cache = None
        # statement parameter values (EXECUTE ... USING), installed by
        # the owning runner: the hoist pass binds BoundParam plan leaves
        # from this tuple, so one cached (value-free) plan re-executes
        # with fresh values through the same warm kernels
        self.exec_params: tuple = ()
        # the newest `_prepare_probe` decision (`_count_lookup`): what
        # `_lookup_lanes` counts the probe buffers under
        self._lookup_decided: Optional[str] = None
        # preemptible sliced execution (exec/sliced/SliceScheduler),
        # installed by the owning runner: leaf page production runs as
        # bounded-work slices with the cooperative boundary (cancel /
        # kill / chaos site `slice`) between them, and scan page
        # capacity is bounded by the slice budget. None = unsliced.
        self.slices = None
        # idempotent-write token (the query id), installed by the owning
        # runner: connector page sinks stage under it and commit on
        # finish, so a retried write attempt can never double-commit
        self.write_token: Optional[str] = None
        # adaptive strategy state (exec/adaptive.AdaptiveQueryState),
        # installed by the owning runner and SHARED across retry
        # attempts: the memory-degrade re-run starts from the modes and
        # heavy keys the failed attempt observed. None = per-execution
        # throwaway state (direct executor use).
        self.adaptive = None
        # device-resident table cache (exec/table_cache.TableCache),
        # installed by the owning runner when table_cache_enabled: hot
        # columns promoted into HBM across queries serve scans with ZERO
        # host->device staging (scan_staging_bytes stays 0 on a hit)
        self.table_cache = None
        # scans promote after this many observed scans of the same
        # (table, columns) working set (session table_cache_min_scans)
        self.table_cache_min_scans = 2
        # per-fragment-attempt memo of resolved table-cache entries
        # (exec/distributed.py shares one dict across a fragment's
        # shard executors, so every shard of one scan sees the SAME
        # hit-or-miss decision); None = resolve per scan (local path)
        self.table_cache_memo: Optional[Dict] = None
        # join dynamic filters routed into connector pruning: scan node
        # id -> TupleDomain registered by the consuming join AFTER its
        # build side collected; the scan's lazy generator intersects it
        # into the split/file/row-group pruning constraint at iteration
        # time (build-before-probe ordering makes that window real)
        self._dyn_domains: Dict[int, object] = {}

    def _checkpoint(self) -> None:
        """Cooperative cancellation/deadline point (page-batch boundary);
        also where a low-memory-killer victim notices its kill mark."""
        if self.deadline is not None:
            self.deadline.check()
        self.memory.poll()

    def _fault_site(self, site: str, detail: str = "") -> None:
        if self.faults is not None:
            self.faults.site(site, detail)

    def _record_spill(self, nbytes: int) -> None:
        """Spill-byte accounting at the host-partition flush sites
        (QueryStats.spilledDataSize analog)."""
        if self.collector is not None:
            self.collector.add_spill(nbytes)

    def _new_spill_store(self, npart: int):
        """A HostPartitionStore charged against the process SpillLedger
        under this query's `spill_max_bytes` budget — spill can no
        longer silently exhaust host RAM (EXCEEDED_SPILL_LIMIT)."""
        from trino_tpu.exec.spill import (SPILL_LEDGER, HostPartitionStore,
                                          resolve_spill_limit)
        return HostPartitionStore(
            npart, ledger=SPILL_LEDGER, query_id=self.memory.query_id,
            limit=resolve_spill_limit(self.session))

    def _adaptive_event(self, name: str, n: int = 1) -> None:
        """Count one adaptive strategy event on the query's collector
        (agg_mode_downgrades / join_recursions / heavy_key_splits /
        spill_fallbacks ... — obs/stats.py)."""
        col = self.collector
        if col is not None:
            setattr(col, name, getattr(col, name) + n)

    def _count_rows(self, name: str, num_rows) -> None:
        """Add a page's row count to the query's counter `name`; a count
        still on the device is read at the query's end, not here."""
        if self.collector is not None:
            self.collector.count_rows(name, num_rows)

    def _count_lookup(self, table: str) -> None:
        """One `_prepare_probe` decision: `row_table`, `position_table`
        or `search`."""
        self._lookup_decided = table
        if self.collector is not None:
            self.collector.count_probe_lookup(table)

    def _lookup_lanes(self, pages) -> Iterator[Page]:
        """The probe buffers a prepared lookup runs over, their lanes
        (capacities: shapes, no sync) summed as `probe_lookup_lanes` —
        and, where the lookup just decided (`_count_lookup`) is `search`,
        as `probe_lookup_lanes_search` too."""
        searched = self._lookup_decided == "search"

        def counted():
            for page in (pages.iter_pages() if hasattr(pages, "iter_pages")
                         else pages):
                if self.collector is not None:
                    self.collector.probe_lookup_lanes += page.capacity
                    if searched:
                        self.collector.probe_lookup_lanes_search += \
                            page.capacity
                yield page
        return counted()

    def _counted(self, stream: "PageStream", name: str) -> Iterator[Page]:
        """`stream`'s pages, their rows counted under `name`."""
        for page in stream.iter_pages():
            self._count_rows(name, page.num_rows)
            yield page

    def _adaptive_span(self, name: str, **attrs) -> None:
        """Emit an instantaneous strategy-switch trace span: every
        adaptive re-decision is a first-class observable event."""
        from trino_tpu.obs.stats import maybe_span
        with maybe_span(self.collector, name, kind="adaptive", **attrs):
            pass

    def _sliced(self, pages):
        """Wrap a leaf page iterator in the slice loop (exec/sliced/):
        every downstream operator — fused streaming chains and blocking
        collects alike — pulls through the leaf, so a boundary here
        preempts the whole pipeline between device dispatches."""
        if self.slices is None:
            return pages
        return self.slices.run(pages, checkpoint=self._checkpoint,
                               fault_site=self._fault_site)

    # ------------------------------------------------- literal hoisting

    def _hoist(self, expr, chain: bool = False):
        """Canonicalize one lowered expression: (literal-free tree,
        runtime values tuple). When hoisting is disabled, statement
        parameters still bind — as baked-in Literals (per-value kernel
        keys, the debugging pin's semantics)."""
        if expr is None:
            return expr, ()
        from trino_tpu.expr.hoist import hoist_literals, materialize_bound
        if not self._hoist_on:
            return materialize_bound(expr, self.exec_params), ()
        return hoist_literals(expr, bound=self.exec_params,
                              like_operands=chain)

    def _hoist_seq(self, exprs, chain: bool = False):
        """Canonicalize a projection list with one shared values tuple."""
        from trino_tpu.expr.hoist import hoist_literal_seq, \
            materialize_bound
        if not self._hoist_on:
            return tuple(materialize_bound(e, self.exec_params)
                         for e in exprs), ()
        return hoist_literal_seq(exprs, bound=self.exec_params,
                                 like_operands=chain)

    # ------------------------------------------------------------ dispatch

    def execute(self, node: PlanNode) -> PageStream:
        name = type(node).__name__
        method = getattr(self, f"_exec_{name}", None)
        if method is None:
            raise ExecutionError(f"no executor for {name}")
        # lowering a node: its programs looked up, its streams built — and
        # a join's build side collected, under its own activities
        with observed_activity("lower_plan", name):
            stream = method(node)
        if self.collector is None or not self.collector.operator_level:
            return stream
        return self._instrument(node, stream)

    def _slot(self, node: PlanNode):
        """The node's OperatorStats slot under operator-level collection
        (blocking nodes hand it to compose_chain as tail_slot so a fused
        tail's device share attributes to them), else None."""
        if self.collector is None or not self.collector.operator_level:
            return None
        return self.collector.register(node)

    def _instrument(self, node: PlanNode, stream: PageStream) -> PageStream:
        """Operator-level stats (EXPLAIN ANALYZE / collect_operator_stats)
        WITHOUT chain splitting (round 13). A streaming node's stream
        still carries its pending fused ops: tag the entries this node
        contributed (the ones its children haven't tagged) with the
        node's stats slot and hand the stream on UNCHANGED — the fused
        chain composes exactly as it would uninstrumented, and
        compose_chain apportions each dispatch's measured device wall
        across the tagged operators by XLA cost analysis. Only
        already-materialized boundaries (leaf scans, blocking operators)
        get the classic counting wrapper: there is no fused chain to
        split there, so per-page row/byte counts and inclusive wall are
        free of observer effects; under EXPLAIN ANALYZE `fence`
        additionally pins their asynchronously dispatched device work."""
        import time as _time

        from trino_tpu.exec.memory import live_page_bytes
        st = self.collector.register(node)
        if stream.pending:
            pending = tuple(
                e if len(e) > 3 and e[3] is not None
                else (e[0], e[1], e[2], st)
                for e in stream.pending)
            return PageStream(stream.pages, stream.symbols, pending)
        fence = self.collector.fence

        def gen():
            it = stream.iter_pages()
            while True:
                t0 = _time.perf_counter()
                try:
                    page = next(it)
                except StopIteration:
                    st.wall_s += _time.perf_counter() - t0
                    return
                if fence:
                    jax.block_until_ready(page)
                n = int(host_read(page.num_rows, "operator_rows"))
                st.output_rows += n
                st.wall_s += _time.perf_counter() - t0
                st.pages += 1
                st.output_bytes += live_page_bytes(page, n)
                yield page
        return PageStream(gen(), stream.symbols)

    # ---------------------------------------------------------------- leaf

    def _exec_TableScanNode(self, node: TableScanNode) -> PageStream:
        conn = self.metadata.connector(node.catalog)
        columns = [c for _, c in node.assignments]
        cap = self._scan_capacity(conn, node)
        symbols = tuple(s for s, _ in node.assignments)
        system = node.catalog == "system"
        st = node.table.name
        tkey = (node.catalog, st.schema, st.table)
        col = self.collector
        # device-resident table cache FIRST: full columns already in HBM
        # serve any column subset at any capacity with zero host->device
        # staging (scan_staging_bytes stays 0 — the counter proof)
        tcache = None if system else self.table_cache
        col_names = [c.name for c in columns]
        # generation snapshot BEFORE any scanning: a promotion built
        # from pre-INSERT pages must not land after the invalidation
        tgen = None if tcache is None else tcache.generation()
        if tcache is not None:
            entry = tcache.lookup(tkey, col_names)
            if entry is not None:
                if col is not None:
                    col.table_cache_hit()

                def gen_resident():
                    # cut when the first page is asked for: a consumer
                    # that walks the entry's columns whole never asks
                    from trino_tpu.exec.table_cache import build_pages
                    with observed_activity("eager_slice", "table_cache"):
                        resident = build_pages(entry, col_names, cap)
                    for page in pulled(resident, "table_cache"):
                        self._checkpoint()
                        yield page

                def whole_resident():
                    return [WholeColumns(
                        tuple(entry.columns[n] for n in col_names),
                        entry.rows, cap)]
                return PageStream(self._sliced(gen_resident()), symbols,
                                  whole=whole_resident)
            if col is not None:
                col.table_cache_miss()
        # a page source that keeps its columns resident is its own cache,
        # and the pages it cuts from them are copies: staging those in
        # the scan cache would hold every column twice
        resident_source = getattr(conn.page_source, "resident_columns",
                                  None)
        cache = None if resident_source is not None else self.scan_cache
        key = None
        if cache is not None and not system:
            # system.runtime tables materialize live engine state at
            # scan time — caching them would freeze it. The key carries
            # the handle's pushed-down constraint and limit: a pruning
            # connector's page set is a function of both, so a LIMIT- or
            # domain-truncated scan must never serve a full one.
            key = (tkey, tuple((c.name, c.ordinal) for c in columns),
                   cap, node.table.constraint.freeze(), node.table.limit)
            staged = cache.get(key)
            if staged is not None:
                if col is not None:
                    col.scan_cache_hit()
                # staged pages are already on device: a hot working set
                # promotes into the table cache from HERE (device
                # concats, no host re-read)
                self._maybe_promote(tcache, tkey, node, staged, tgen)

                def gen_hit(pages=staged):
                    for page in pulled(pages, "scan_cache"):
                        self._checkpoint()
                        yield page
                return PageStream(self._sliced(gen_hit()), symbols)
            if col is not None:
                col.scan_cache_miss()
        gen_seen = None if key is None else cache.generation()

        def gen():
            from trino_tpu.exec.memory import page_bytes
            # dynamic filters (registered by a consuming join after its
            # build collected — strictly before this generator is
            # pulled) intersect into the pruning constraint so the
            # connector can skip whole files/row groups, not just rows
            handle, dyn_applied = self._effective_handle(conn, node)
            splits = conn.split_manager.get_splits(handle, target_splits=1)
            # promotion decision up front: a FULL page set (no limit, no
            # effective pruning) of a hot-enough working set stages for
            # the device table cache even when the scan cache is off
            promote = False
            if tcache is not None and not dyn_applied \
                    and node.table.limit is None \
                    and (not getattr(conn.metadata, "supports_zone_maps",
                                     False)
                         or handle.constraint.is_all()):
                count = tcache.note_scan(tkey, col_names)
                promote = count >= max(
                    int(self.table_cache_min_scans), 1) \
                    and tcache.should_promote(tkey, col_names)
            staging = [] if (key is not None and not dyn_applied) \
                or promote else None
            # session verify level + the query's fault injector ride a
            # connector thread-local down to the read path (the SPI scan
            # signature carries no session); reset in the finally so a
            # later bare read on this thread falls back to the default
            setopt = getattr(conn, "set_scan_options", None)
            if setopt is not None:
                setopt(verify=str(self.session.get(
                           "lake_verify_checksums")),
                       faults=self.faults)
            try:
                for split in splits:
                    self._fault_site("scan", str(node.table))
                    for page, moved in count_host_staging(pulled(
                            conn.page_source.pages(split, columns, cap),
                            "connector")):
                        self._checkpoint()
                        if col is not None:
                            col.add_scan_staging(page_bytes(page), moved)
                        if promote and not staging and key is None \
                                and not tcache.admits(
                                    page, max(self._table_rows(conn, node),
                                              1)):
                            # by shapes alone it cannot be admitted (SF10
                            # lineitem against 1 GiB): gather nothing
                            promote, staging = False, None
                        if staging is not None:
                            staging.append(page)
                        yield page
            finally:
                self._drain_scan_stats(conn)
                if setopt is not None:
                    setopt()
            if staging is not None and key is not None and not dyn_applied:
                # gen_seen guards the race with a concurrent INSERT: a
                # scan that started pre-change must not publish post-
                # invalidation (same discipline as PlanCache.put). A
                # dynamically-pruned page set is keyed on the STATIC
                # constraint, so it must not publish at all.
                cache.put(key, staging, gen=gen_seen)
            if promote and staging:
                counts = [int(c) for c in host_read(
                    [p.num_rows for p in staging], "promote_counts")]
                tcache.promote_from_pages(
                    tkey, [(c.name, c) for _, c in node.assignments],
                    staging, counts, device=self.mem_device,
                    collector=col, gen=tgen)

        def whole_connector():
            if resident_source is None:
                return None
            handle, dyn_applied = self._effective_handle(conn, node)
            if dyn_applied:
                return None
            parts = []
            for split in conn.split_manager.get_splits(handle,
                                                       target_splits=1):
                mark = host_staged_bytes()
                with observed_activity("page_pull", "connector"):
                    got = resident_source(split, columns, cap)
                if got is None:
                    return None
                parts.append(WholeColumns(
                    got[0], got[1], cap, sum(c.nbytes for c in got[0]),
                    host_staged_bytes() - mark))
            return parts
        return PageStream(self._sliced(gen()), symbols,
                          whole=whole_connector)

    def _effective_handle(self, conn, node: TableScanNode):
        """(handle for split pruning, dynamic-filter-applied flag): the
        static pushed-down constraint, intersected with any registered
        join dynamic filter, or cleared entirely when the session pins
        zone-map pruning off (lake_zone_maps_enabled = false)."""
        import dataclasses as _dc

        from trino_tpu.predicate import TupleDomain
        handle = node.table
        prunes = getattr(conn.metadata, "supports_zone_maps", False)
        if prunes and not bool(
                self.session.get("lake_zone_maps_enabled")):
            return (_dc.replace(handle, constraint=TupleDomain.all()),
                    False)
        dyn = self._dyn_domains.get(id(node))
        if dyn is None or not prunes:
            return handle, False
        return (_dc.replace(handle,
                            constraint=handle.constraint.intersect(dyn)),
                True)

    def _drain_scan_stats(self, conn) -> None:
        """Fold the connector's per-scan prune counters (thread-local —
        the scan ran on this thread) into the query stats."""
        take = getattr(conn, "take_scan_stats", None)
        if take is None:
            return
        d = take() or {}
        if self.collector is not None and d:
            self.collector.add_pruned(d.get("files_pruned", 0),
                                      d.get("row_groups_pruned", 0))

    def _maybe_promote(self, tcache, tkey, node: TableScanNode,
                       pages, gen=None) -> None:
        """Promote a hot (table, columns) working set into the device
        table cache from its already-staged pages. Only FULL page sets
        are admissible: a handle with a pushed-down constraint or limit
        on a pruning connector may cover a subset of the table."""
        if tcache is None or not pages:
            return
        if node.table.limit is not None:
            return
        if getattr(self.metadata.connector(node.catalog).metadata,
                   "supports_zone_maps", False) \
                and not node.table.constraint.is_all():
            return
        names = [c.name for _, c in node.assignments]
        if tcache.note_scan(tkey, names) < max(
                int(self.table_cache_min_scans), 1):
            return
        if not tcache.should_promote(tkey, names):
            return
        counts = [int(c) for c in host_read(
            [p.num_rows for p in pages], "promote_counts")]
        tcache.promote_from_pages(
            tkey, [(c.name, c) for _, c in node.assignments], pages,
            counts, device=self.mem_device, collector=self.collector,
            gen=gen)

    def register_dynamic_domain(self, scan_node, column: str, typ,
                                lo, hi) -> None:
        """A consuming join publishes its collected build-side key range
        as a TupleDomain for `scan_node` — the scan's generator (not yet
        pulled: build-before-probe) folds it into file/row-group
        pruning. Values are raw internal representation, matching the
        zone maps."""
        from trino_tpu.predicate import Domain, Range, TupleDomain
        dom = TupleDomain.with_column_domains(
            {column: Domain.from_range(typ, Range.between(lo, hi))})
        prev = self._dyn_domains.get(id(scan_node))
        self._dyn_domains[id(scan_node)] = \
            dom if prev is None else prev.intersect(dom)
        from trino_tpu.obs.stats import maybe_span
        with maybe_span(self.collector, "dynamic-filter-pushdown",
                        kind="scan", column=column, low=str(lo),
                        high=str(hi)):
            pass

    def _dyn_scan_target(self, subtree, symbol_name: str):
        """The TableScanNode under `subtree` whose output directly
        carries `symbol_name`, reached only through row-restricting
        nodes (filter/project/join/semijoin — pruning its rows by a key
        bound the join will enforce anyway cannot change results; a
        window/limit/topn in between could, so the walk stops there),
        on a connector that prunes by zone maps. None when absent."""
        from trino_tpu.planner.nodes import (FilterNode, JoinNode,
                                             ProjectNode, SemiJoinNode)
        stack = [subtree]
        while stack:
            n = stack.pop()
            if isinstance(n, TableScanNode):
                for s, ch in n.assignments:
                    if s.name == symbol_name:
                        conn = self.metadata.connector(n.catalog)
                        if getattr(conn.metadata, "supports_zone_maps",
                                   False):
                            return n, ch.name, ch.type
                continue
            if isinstance(n, (FilterNode, ProjectNode, JoinNode,
                              SemiJoinNode)):
                stack.extend(n.sources)
        return None

    @staticmethod
    def _table_rows(conn, node: TableScanNode) -> int:
        """The table's row count by the connector's statistics, else 0."""
        try:
            stats = conn.metadata.get_table_statistics(node.table)
            return int(stats.row_count) if stats and stats.row_count else 0
        except Exception:
            return 0

    def _scan_capacity(self, conn, node: TableScanNode) -> int:
        """Size scan pages to the table: one big page per split keeps the
        steady state at a handful of device calls instead of a Python loop
        over thousands of 64Ki pages (ScanFilterAndProjectOperator's whole
        point is amortizing per-page overhead; on TPU the analog is fewer,
        larger fused kernel launches)."""
        cap = self.page_capacity
        rows = self._table_rows(conn, node)
        if rows > cap:
            max_cap = int(self.session.get("scan_page_capacity"))
            cap = min(_next_pow2(rows), max_cap)
        if self.slices is not None:
            # one scan page must never exceed a slice: a bigger page is
            # a single un-preemptible kernel launch, exactly what the
            # sliced executor exists to bound
            cap = min(cap, self.slices.capacity_cap(self.page_capacity))
        return cap

    def _exec_ValuesNode(self, node: ValuesNode) -> PageStream:
        cols = []
        n = len(node.rows)
        cap = max(_next_pow2(n), 8)
        for i, sym in enumerate(node.symbols):
            typ = sym.type
            vals = []
            valid = []
            for row in node.rows:
                lit = row[i]
                if not isinstance(lit, Literal):
                    raise ExecutionError("VALUES row is not literal")
                vals.append(0 if lit.value is None else lit.value)
                valid.append(lit.value is not None)
            if T.is_string(typ):
                from trino_tpu.page import Dictionary
                d, codes = Dictionary.build(np.asarray(
                    [v if isinstance(v, str) else "" for v in vals],
                    dtype=object))
                arr = np.zeros(cap, dtype=np.int32)
                arr[:n] = codes
                col = Column(jnp.asarray(arr), _valid_arr(valid, cap), typ, d)
            else:
                arr = np.zeros(cap, dtype=T.to_numpy_dtype(typ))
                arr[:n] = vals
                col = Column(jnp.asarray(arr), _valid_arr(valid, cap), typ,
                             None)
            cols.append(col)
        page = Page(tuple(cols), n)
        return PageStream(iter([page]), node.symbols)

    # ----------------------------------------------------------- streaming

    def _exec_FilterNode(self, node: FilterNode) -> PageStream:
        # Filter(SemiJoin) fuses into semi/anti probe (LocalExecutionPlanner
        # visitFilter's special-cased semi-join consumption); complex match
        # usage (the flag inside OR/CASE — q10/q35-style stacked EXISTS)
        # falls back to the generic mark-column path
        if isinstance(node.source, SemiJoinNode) and \
                self._semijoin_filter_mode(node) is not None:
            return self._exec_semijoin_filter(node)
        src = self.execute(node.source)
        lay, typ = _layout(src.symbols)
        pred, prm = self._hoist(lower_expr(node.predicate, lay, typ),
                                chain=True)
        tag = "agg-having" if isinstance(node.source, AggregationNode) \
            else "filter"
        return PageStream(
            src.pages, src.symbols,
            src.pending + (((tag, pred),
                            lambda: lambda p, g, f=compile_filter(pred):
                            p.filter(f(p, g)), prm),), src.whole)

    def _exec_ProjectNode(self, node: ProjectNode) -> PageStream:
        src = self.execute(node.source)
        lay, typ = _layout(src.symbols)
        exprs, prm = self._hoist_seq(
            tuple(lower_expr(e, lay, typ) for _, e in node.assignments),
            chain=True)

        def builder():
            fns = [compile_expression(e) for e in exprs]
            return lambda page, g: Page(tuple(fn(page, g) for fn in fns),
                                        page.num_rows)
        return PageStream(src.pages, tuple(s for s, _ in node.assignments),
                          src.pending + ((("project", exprs), builder,
                                          prm),), src.whole)

    def _exec_LimitNode(self, node: LimitNode) -> PageStream:
        src = self.execute(node.source)

        def gen():
            remaining = node.count
            for page in src.iter_pages():
                n = int(host_read(page.num_rows, "limit_rows"))
                if n >= remaining:
                    yield Page(page.columns, remaining)
                    return
                remaining -= n
                yield page
        return PageStream(gen(), src.symbols)

    def _exec_OffsetNode(self, node: OffsetNode) -> PageStream:
        src = self.execute(node.source)

        def gen():
            to_skip = node.count
            for page in src.iter_pages():
                n = int(host_read(page.num_rows, "offset_rows"))
                if to_skip >= n:
                    to_skip -= n
                    continue
                if to_skip > 0:
                    idx = jnp.arange(page.capacity, dtype=jnp.int32) + to_skip
                    gathered = tuple(c.gather(idx) for c in page.columns)
                    page = Page(gathered, n - to_skip)
                    to_skip = 0
                yield page
        return PageStream(gen(), src.symbols)

    # ------------------------------------------------------------ blocking

    def _collect(self, stream: PageStream) -> Optional[Page]:
        """Materialize a stream (blocking-operator input). The result is
        reserved against query_max_memory: blocking materializations are
        what consumes HBM (streamed pages flow through one fused kernel).
        Freed at operator scope via _free_collected."""
        from trino_tpu.exec.memory import page_bytes
        page = self.merge_counted(list(stream.iter_pages()))
        if page is None:
            return None
        # chaos site `memory`: injected node-pool pressure at the point a
        # real reservation would hit the killer
        self._fault_site("memory", "collect")
        self.memory.reserve(page_bytes(page), "collect",
                            device=self.mem_device)
        return page

    def merge_counted(self, pages: List[Page]) -> Optional[Page]:
        """Concatenate pages ON DEVICE (dynamic_update_slice cascade) with
        ONE batched count fetch — the host bounce (concat_pages) moved
        every live row to the host and back, and a per-page num_rows
        check is a device sync each. Pages shrink to their live pow2
        first so the concat transient is O(live rows), not O(sum of scan
        capacities). Shared by blocking collects and the distributed
        runner's per-shard fragment outputs."""
        page, _ = self.merge_counted_rows(pages)
        return page

    def merge_counted_rows(self, pages: List[Page]
                           ) -> Tuple[Optional[Page], int]:
        """merge_counted plus the live total it already fetched — the
        adaptive aggregation's reduction-ratio denominator, free at
        every compaction boundary."""
        if not pages:
            return None, 0
        with observed_activity("page_concat"):
            counts = [int(c) for c in host_read(
                [p.num_rows for p in pages], "merge_counts")]
            total = sum(counts)
            if total == 0:
                return None, 0
            live = [self._tight(p, c)
                    for p, c in zip(pages, counts) if c > 0]
            return self._merge_buf(live, total), total

    @staticmethod
    def _tight_capacity(page: Page, n: int) -> int:
        """The capacity a page of `n` live rows is worth: the pow2
        envelope of `n` where the page is more than twice that, else the
        page's own (a rung below is not worth a program of its own)."""
        tight = _next_pow2(max(n, 1))
        return tight if page.capacity > 2 * tight else page.capacity

    @classmethod
    def _tight(cls, page: Page, n: int) -> Page:
        """Shrink a page to the pow2 envelope of its live count (one
        eager device slice a column; downstream sorts/builds then run at
        live size)."""
        with observed_activity("eager_slice", "shrink_to"):
            return page.shrink_to(cls._tight_capacity(page, n))

    def _device_concat(self, pages: List[Page]) -> Page:
        """Jitted device-side page concatenation (page.device_concat) —
        one compiled program per (capacities, ncols) combination."""
        from trino_tpu.page import device_concat
        key = ("dconcat", tuple(p.capacity for p in pages),
               pages[0].num_columns)
        op = cached_kernel(key, lambda: lambda *ps: device_concat(ps))
        return op(*pages)

    def _coalesce_stream(self, stream: PageStream,
                         target_rows: Optional[int] = None,
                         prefilter=None) -> PageStream:
        """Batch filtered pages into few large buffers before a probe.

        A probe kernel launch has a large fixed cost (sort-engine passes at
        static capacity, regardless of live rows): round-4 profiling showed
        q3 SF10 paying ~23s across 19 per-page probe calls on ~2M-live
        pages. Lookahead windows keep the transfer discipline (one batched
        count fetch per window, JAX dispatch stays async).

        `prefilter` is an optional (op, args) dynamic filter (build-side
        key range) that measures before it moves anything: `op` yields each
        page's keep mask and count, fetched with the window's row counts
        in the one transfer. It is adaptive — if the first window's masks
        prune less than 25% of its rows, the filter is dropped for the
        stream and the window's pages go on as they came (no gather ran;
        the NULL keys it would have dropped fall to the probe's own
        liveness test, as on every later page). A filter that is kept
        compacts each page by its fetched count (`_compact_counted`)."""
        if target_rows is None:
            target_rows = int(self.session.get("probe_coalesce_rows"))
        row_bytes = 8 * max(len(stream.symbols), 1)
        # cap a buffer at ~512MB regardless of width: the probe's stable
        # sort carries every column as payload, and a wider buffer's sort
        # scratch is what exhausted the device at SF100 (measured: 21M-row
        # x 11-operand sorts fail, 4M-row buffers stream 600M rows fine)
        target_rows = max(1 << 16, min(target_rows, (1 << 29) // row_bytes))

        def gen():
            import itertools
            it = stream.iter_pages()
            buf: List[Page] = []
            buf_rows = 0
            use_df = prefilter is not None
            df_measured = False
            while True:
                window = list(itertools.islice(it, 8))
                if not window:
                    break
                if use_df:
                    pf_op, pf_args = prefilter
                    masks = [pf_op(p, *pf_args) for p in window]
                    fetched = host_read(
                        ([p.num_rows for p in window],
                         [k for _, k in masks]), "coalesce_counts")
                    live, kept = ([int(c) for c in cs] for cs in fetched)
                    if not df_measured:
                        df_measured = True
                        if sum(kept) > 0.75 * max(sum(live), 1):
                            use_df = False   # not selective enough
                    if use_df:
                        # a page that keeps nothing is dropped below
                        window = [
                            self._compact_counted(p, m, k, n, "dfrange")
                            if k else p
                            for p, (m, _), k, n in zip(
                                window, masks, kept, live)]
                        counts = kept
                    else:
                        counts = live
                else:
                    counts = host_read([p.num_rows for p in window],
                                       "coalesce_counts")
                for p, c in zip(window, counts):
                    n = int(c)
                    if n == 0:
                        continue
                    if n >= target_rows:
                        yield self._merge_buf([p], n)
                        continue
                    buf.append(self._tight(p, n))
                    buf_rows += n
                    if buf_rows >= target_rows:
                        yield self._merge_buf(buf, buf_rows)
                        buf, buf_rows = [], 0
            if buf:
                yield self._merge_buf(buf, buf_rows)
        return PageStream(gen(), stream.symbols)

    def _merge_buf(self, buf: List[Page], rows: int) -> Page:
        with observed_activity("page_concat"):
            page = buf[0] if len(buf) == 1 else self._device_concat(buf)
            page = self._tight(page, rows)
            if isinstance(page.num_rows, int):
                # a scan page counts its rows in a Python int (a weak
                # int64 to a trace), a concatenation in an int32: whether
                # a table arrives as one page or as two depends on the
                # slice budget, which the clock retunes (exec/sliced/
                # scheduler.py), so a blocking operator would compile
                # twice for one shape — TPC-H Q18's customer build did,
                # 37 s into its second execution
                page = Page(page.columns, np.int32(page.num_rows))
            return page

    def _free_collected(self, page: Optional[Page]) -> None:
        """Release a _collect reservation at operator scope (the reference
        frees per-operator memory contexts on finish — without this a
        query's sequential peak would be accounted as the SUM of every
        build side / sort input ever held)."""
        if page is not None:
            from trino_tpu.exec.memory import page_bytes
            self.memory.free(page_bytes(page), "collect",
                             device=self.mem_device)

    def _exec_AggregationNode(self, node: AggregationNode) -> PageStream:
        src = self.execute(node.source)
        lay, typ = _layout(src.symbols)
        key_channels = [lay[s.name] for s in node.group_by]
        specs = []
        for out_sym, call in node.aggregations:
            if call.args:
                arg = call.args[0]
                assert isinstance(arg, SymbolRef)
                input_ch: Optional[int] = lay[arg.name]
                in_type: Optional[T.Type] = typ[arg.name]
            else:
                input_ch, in_type = None, None
            in2_ch = in2_type = None
            if len(call.args) > 1:
                arg2 = call.args[1]
                assert isinstance(arg2, SymbolRef)
                in2_ch, in2_type = lay[arg2.name], typ[arg2.name]
            mask_ch = None
            if call.filter is not None:
                assert isinstance(call.filter, SymbolRef)
                mask_ch = lay[call.filter.name]
            specs.append(AggSpec(call.name, input_ch, in_type, mask_ch,
                                 call.distinct, in2_ch, in2_type))

        key_channels_t = tuple(key_channels)
        specs_t = tuple(specs)
        from trino_tpu.ops.aggregate import (COLLECT_AGGREGATES,
                                             SINGLE_STEP_AGGREGATES,
                                             group_max_size)
        if any(s.distinct or s.name in SINGLE_STEP_AGGREGATES
               for s in specs):
            # DISTINCT needs every row of a group in one kernel call
            # (distinctness is a property of the whole group, not a page),
            # so collect and run one SINGLE-step aggregation — the
            # MarkDistinct + filtered-agg plan collapsed into the sort-based
            # kernel (ops/aggregate.py:_distinct_first_mask). Collect
            # aggregates (array_agg/histogram/map_agg) additionally size
            # their list layout with a max-group-size pre-pass.
            needs_len = any(s.name in COLLECT_AGGREGATES for s in specs)

            def gen_distinct():
                page = self._collect(src)
                if page is None:
                    if not key_channels:
                        yield self._empty_global_agg(node, specs)
                    return
                L = None
                if needs_len:
                    szop = cached_kernel(
                        ("agg-groupmax", key_channels_t),
                        lambda: group_max_size(key_channels))
                    got = max(int(host_read(szop(page), "group_max_size")), 1)
                    # small pow2 (not the 1024-floor page helper): the
                    # element plane is [capacity, L]
                    L = 1 << (got - 1).bit_length() if got > 1 else 1
                single_op = cached_kernel(
                    ("agg-single", key_channels_t, specs_t, L),
                    lambda: hash_aggregate(key_channels, specs,
                                           Step.SINGLE, list_len=L))
                try:
                    yield single_op(page)
                finally:
                    self._free_collected(page)
            return PageStream(gen_distinct(), node.outputs)
        # fuse the upstream filter/project chain into the partial-agg kernel:
        # scan -> filter -> project -> partial agg is ONE device program per
        # page (ScanFilterAndProjectOperator + partial-agg fusion). The
        # aggregate reads liveness from row_mask() alone, so when every
        # fused step is lane-wise the filters compact nothing and hand it
        # a selection mask (chain_defers_compaction)
        partial_key = ("agg-partial", key_channels_t, specs_t)

        def partial_builder():
            return hash_aggregate(key_channels, specs, Step.PARTIAL)
        partial_op = compose_chain(src.pending, partial_key, partial_builder,
                                   tail_slot=self._slot(node))
        # the adaptive bypass kernel: same fused chain, but the tail maps
        # each row to a PARTIAL-layout state row with NO sort (O(n) — the
        # "Partial Partial Aggregates" bypass for effectively-high NDV);
        # layout-identical to partial_op's output so both mix in one buffer.
        # Its filters keep compacting: it emits a state row per input row
        # under page.num_rows, so the live rows must be a prefix
        from trino_tpu.ops.aggregate import passthrough_partial
        bypass_op = compose_chain(
            src.pending, ("agg-bypass", key_channels_t, specs_t),
            lambda: passthrough_partial(key_channels, specs),
            tail_slot=self._slot(node))

        # FINAL consumes the partial layout: keys first, then each agg's
        # state columns in sequence
        from trino_tpu.ops.aggregate import get_aggregate
        nkeys = len(key_channels)
        state_channels = []
        ch = nkeys
        for spec in specs:
            fn = get_aggregate(spec.name, spec.input_type)
            k = len(fn.state(spec.input_type))
            state_channels.append(list(range(ch, ch + k)))
            ch += k
        final_keys = list(range(nkeys))
        final_kernel = cached_kernel(
            ("agg-final", nkeys, specs_t),
            lambda: hash_aggregate(final_keys, specs, Step.FINAL,
                                   state_channels))

        def final_op(page: Page) -> Page:
            out = final_kernel(page)
            self._count_rows("aggregate_groups_out", out.num_rows)
            return out

        intermediate_op = cached_kernel(
            ("agg-intermediate", nkeys, specs_t),
            lambda: hash_aggregate(final_keys, specs, Step.INTERMEDIATE,
                                   state_channels))

        def gen():
            # no per-page num_rows sync: empty pages produce neutral partial
            # states that merge correctly (the sync stalled the dispatch
            # queue once per page). Over-budget partial buffers compact
            # via Step.INTERMEDIATE; if groups aren't collapsing (q18-class
            # high-cardinality GROUP BY) the compacted states spill to host
            # hash partitions and finalize one bounded partition at a time
            # (SpillableHashAggregationBuilder.java:47 re-thought — see
            # exec/spill.py). ADAPTIVE: an AggModeController watches the
            # observed reduction ratio at every compaction boundary and
            # walks full -> shrunken -> bypass (exec/adaptive.py) when NDV
            # turns out effectively high, re-upgrading when it recovers;
            # decisions happen only between device dispatches, so the
            # sliced executor's cooperative boundary stays responsive.
            from trino_tpu.exec.adaptive import (AdaptiveQueryState,
                                                 AggMode)
            from trino_tpu.exec.memory import page_bytes
            from trino_tpu.exec.spill import partition_by_hash
            threshold = int(self.session.get("agg_spill_threshold_bytes"))
            npart = int(self.session.get("spill_partition_count"))
            spillable = bool(self.session.get("spill_enabled")) \
                and bool(key_channels)
            ctl = None
            # adaptive modes only when spill can absorb them: without a
            # flush boundary there is no observation to correct a wrong
            # CBO estimate, and shrunken/bypass states would accumulate
            # O(rows) with nothing bounding them
            if bool(self.session.get("adaptive_partial_agg")) \
                    and spillable:
                state = self.adaptive if self.adaptive is not None \
                    else AdaptiveQueryState()
                # STRUCTURAL key (group-by + aggregate output symbol
                # names), not node id: a degrade re-run that re-plans
                # past a missed plan cache must still find the failed
                # attempt's controller; the output symbols disambiguate
                # two operators grouping by the same keys
                ctl = state.agg_controller(
                    ("agg", tuple(s.name for s in node.group_by),
                     tuple(s.name for s, _ in node.aggregations)),
                    ndv=getattr(node, "ndv_estimate", None),
                    rows=getattr(node, "rows_estimate", None),
                    allow_bypass=spillable)
            store = None
            part_ops: Dict[int, object] = {}
            buf: List[Page] = []
            buf_bytes = 0
            any_pages = False
            # the ratio denominator is RAW input rows in EVERY mode
            # (full's per-page partial must not shrink it, or
            # key-clustered input oscillates between metrics): raw page
            # counts batch-fetch at the compaction boundary, and a
            # re-buffered compacted page carries its history forward
            raw_counts: List[object] = []
            raw_carry = 0

            def part_op_for(salt: int):
                op = part_ops.get(salt)
                if op is None:
                    op = part_ops[salt] = cached_kernel(
                        ("agg-spill-part", nkeys, npart, salt),
                        lambda: partition_by_hash(final_keys, npart,
                                                  salt=salt))
                return op

            def compact_buffer():
                nonlocal buf, buf_bytes
                merged, rows_in = self.merge_counted_rows(buf)
                buf, buf_bytes = [], 0
                if merged is None:
                    return None, rows_in, 0
                out = intermediate_op(merged)
                n = int(host_read(out.num_rows, "agg_compact_rows"))
                if n == 0:
                    return None, rows_in, 0
                return self._tight(out, n), rows_in, n

            def raw_rows_in():
                nonlocal raw_counts, raw_carry
                total = raw_carry + sum(
                    int(c) for c in host_read(raw_counts, "agg_raw_rows")) \
                    if raw_counts else raw_carry
                raw_counts = []
                return total

            def observe(rows_in, groups_out):
                if ctl is None or rows_in <= 0:
                    return
                transition = ctl.observe(rows_in, groups_out)
                if transition is not None:
                    self._adaptive_event(
                        "agg_mode_downgrades" if transition == "downgrade"
                        else "agg_mode_upgrades")
                    self._adaptive_span(
                        "agg-mode-switch", transition=transition,
                        mode=ctl.mode,
                        ratio=round(ctl.last_ratio or 0.0, 4))

            def spill(combined):
                nonlocal store
                self._fault_site("spill", "agg")
                self._record_spill(page_bytes(combined))
                if store is None:
                    store = self._new_spill_store(npart)
                sorted_pg, counts = part_op_for(0)(combined)
                store.spill_partitioned(sorted_pg,
                                        host_read(counts, "spill_counts"))

            try:
                # a scan of resident columns into a direct aggregate is
                # one launch, its pages walked inside (`compose_walk`);
                # the states come back as one small page
                walked = None if ctl is not None and ctl.mode != AggMode.FULL \
                    else self._walked(src, partial_key, partial_builder)
                for pp in walked or ():
                    any_pages = True
                    buf.append(pp)
                for page in src.pages if walked is None else ():
                    self._checkpoint()
                    any_pages = True
                    mode = ctl.mode if ctl is not None else AggMode.FULL
                    pp = partial_op(page) if mode == AggMode.FULL \
                        else bypass_op(page)
                    buf.append(pp)
                    raw_counts.append(page.num_rows)
                    buf_bytes += page_bytes(pp)
                    if not (spillable and buf_bytes >= threshold):
                        continue
                    if mode == AggMode.BYPASS:
                        probe = ctl.should_probe()
                        ctl.note_flush()
                        if not probe:
                            # full bypass: raw per-row states straight to
                            # host partitions — zero reduction work (the
                            # per-partition finalize groups ONCE)
                            merged, _ = self.merge_counted_rows(buf)
                            buf, buf_bytes = [], 0
                            raw_counts, raw_carry = [], 0
                            if merged is not None:
                                spill(merged)
                            continue
                    elif ctl is not None:
                        ctl.note_flush()
                    rows_raw = raw_rows_in()
                    combined, _rows_states, groups_out = compact_buffer()
                    observe(rows_raw, groups_out)
                    if combined is None:
                        raw_carry = 0
                        continue
                    cb = page_bytes(combined)
                    if cb >= threshold // 2:
                        spill(combined)        # groups aren't collapsing
                        raw_carry = 0
                    else:
                        buf, buf_bytes = [combined], cb
                        raw_carry = rows_raw   # history rides along

                if store is None:
                    if not any_pages:
                        if not key_channels:
                            yield self._empty_global_agg(node, specs)
                        return
                    merged, _ = self.merge_counted_rows(buf)
                    if merged is None:
                        # every input page was empty (grouped agg -> no
                        # output; global agg partials always carry one
                        # state row, so a None merge implies zero rows)
                        if not key_channels:
                            yield self._empty_global_agg(node, specs)
                        return
                    yield final_op(merged)
                    return
                rows_raw = raw_rows_in()
                combined, _rows_states, groups_out = compact_buffer()
                observe(rows_raw, groups_out)
                if combined is not None:
                    spill(combined)
                yield from self._finalize_agg_spill(
                    store, 0, final_op, intermediate_op, part_op_for,
                    final_keys, threshold)
            finally:
                if store is not None:
                    store.close()
        return PageStream(gen(), node.outputs)

    def _walked(self, src: PageStream, tail_key, tail_builder
                ) -> Optional[Iterator[Page]]:
        """The partial states of `src`'s chain under the aggregate
        `tail_key`, one page a launch over a span of the scan's whole
        columns (`compose_walk`) — or None where the chain takes a page a
        launch: the scan offers no resident columns (or operator-level
        collection stands between), they are one page, a buffer is
        shorter than its pages, or the chain cannot walk. Between
        launches: the slice boundary and the checkpoint, as between
        pages."""
        parts = src.whole() if src.whole is not None else None
        if not parts:
            return None
        ops = []
        for part in parts:
            pages = -(-part.rows // part.capacity)
            if pages < 2 or any(c.capacity < pages * part.capacity
                                for c in part.columns):
                return None
            op = compose_walk(src.pending, tail_key, tail_builder,
                              next(column_spans(*part[:3])))
            if op is None:
                return None
            ops.append(op)

        def launches():
            for part, op in zip(parts, ops):
                if part.staged:
                    self._fault_site("scan", "whole columns")
                    if self.collector is not None:
                        self.collector.add_scan_staging(part.staged,
                                                        part.moved)
                for span in self._sliced(column_spans(*part[:3])):
                    self._checkpoint()
                    yield op(span)
        return launches()

    def _finalize_agg_spill(self, store, depth: int, final_op,
                            intermediate_op, part_op_for, key_idxs,
                            threshold: int) -> Iterator[Page]:
        """Finalize spilled hash partitions with the robust dynamic
        hybrid discipline: a partition within budget restages and
        finalizes in one kernel; one still over budget first splits out
        heavy-hitter keys (re-hashing can NEVER separate one key's rows
        — they fold chunk-wise instead, INTERMEDIATE collapses a heavy
        key to ONE state row per chunk), then recursively repartitions
        with a fresh hash salt up to `spill_max_recursion`, and at max
        depth falls back to the bounded chunked fold — graceful
        degradation instead of an over-budget restage OOM."""
        from trino_tpu.exec.memory import page_bytes
        from trino_tpu.exec.spill import (detect_partition_heavy_keys,
                                          partition_key_hashes,
                                          split_partition)
        threshold = self._spill_budget(threshold)
        max_rec = int(self.session.get("spill_max_recursion"))
        heavy_limit = int(self.session.get("spill_heavy_key_limit"))
        npart = store.npart

        def stage_final(p: int, nrows: int) -> Iterator[Page]:
            pg = store.restage(p, _next_pow2(max(nrows, 1)))
            store.drop(p)
            held = page_bytes(pg)
            self.memory.reserve(held, "agg-restage",
                                device=self.mem_device)
            try:
                yield final_op(pg)
            finally:
                self.memory.free(held, "agg-restage",
                                 device=self.mem_device)

        for p in range(npart):
            self._checkpoint()
            nrows = store.partition_rows(p)
            if nrows == 0:
                continue
            if store.partition_bytes(p) <= max(threshold, 1):
                yield from stage_final(p, nrows)
                continue
            chunk_rows = store.chunk_rows_for(p, threshold)
            if heavy_limit > 0 and depth < max_rec and npart > 1:
                hashes = partition_key_hashes(store, p, key_idxs)
                heavy = detect_partition_heavy_keys(
                    store, p, key_idxs, heavy_limit,
                    max(2, nrows // (2 * max(npart, 2))),
                    piece_hashes=hashes)
                if len(heavy):
                    self._fault_site("spill", "agg-heavy")
                    self._adaptive_event("heavy_key_splits")
                    self._adaptive_span("agg-heavy-split", depth=depth,
                                        keys=int(len(heavy)))
                    sub = split_partition(store, p, key_idxs, heavy,
                                          piece_hashes=hashes)
                    try:
                        yield from self._agg_chunk_fold(
                            sub, 0, final_op, intermediate_op,
                            chunk_rows)
                    finally:
                        sub.close()
                    nrows = store.partition_rows(p)
                    if nrows == 0:
                        continue
                    if store.partition_bytes(p) <= max(threshold, 1):
                        yield from stage_final(p, nrows)
                        continue
            if depth >= max_rec or npart <= 1:
                # bounded-depth guarantee: an irreducible partition
                # folds in bounded chunks instead of recursing forever
                # (npart <= 1: re-hashing cannot redistribute at all)
                self._fault_site("spill", "agg-fallback")
                self._adaptive_event("spill_fallbacks")
                self._adaptive_span("agg-spill-fallback", depth=depth)
                yield from self._agg_chunk_fold(
                    store, p, final_op, intermediate_op, chunk_rows)
                continue
            # recursive repartition under a fresh hash salt: the same
            # keys REDISTRIBUTE across the child partitions
            self._fault_site("spill", "agg-recurse")
            self._adaptive_event("agg_recursions")
            self._adaptive_span("agg-spill-recurse", depth=depth + 1)
            child = self._new_spill_store(npart)
            try:
                op = part_op_for(depth + 1)
                # drain: each transferred piece releases before the
                # child charges the next — no transient double-hold of
                # the partition against the spill budget
                for chunk in store.drain_partition_chunks(p, chunk_rows):
                    self._checkpoint()
                    sorted_pg, counts = op(chunk)
                    child.spill_partitioned(sorted_pg,
                                            host_read(counts, "spill_counts"))
                store.drop(p)
                yield from self._finalize_agg_spill(
                    child, depth + 1, final_op, intermediate_op,
                    part_op_for, key_idxs, threshold)
            finally:
                child.close()

    def _agg_chunk_fold(self, store, p: int, final_op, intermediate_op,
                        chunk_rows: int) -> Iterator[Page]:
        """Bounded chunked merge of one partition: restage <= chunk_rows
        at a time, INTERMEDIATE-fold into the carried state, finalize
        once — the device transient is one chunk plus the state (which
        is the partition's true group count, the output-size floor no
        strategy can beat). The heavy-key path and the max-recursion
        fallback both bottom out here."""
        state = None
        for chunk in store.drain_partition_chunks(p, chunk_rows):
            self._checkpoint()
            merged = chunk if state is None \
                else self._device_concat([state, chunk])
            out = intermediate_op(merged)
            n = int(host_read(out.num_rows, "agg_compact_rows"))
            state = self._tight(out, n) if n else None
        store.drop(p)
        if state is not None:
            yield final_op(state)

    def _empty_global_agg(self, node: AggregationNode, specs) -> Page:
        cols = []
        for (sym, call), spec in zip(node.aggregations, specs):
            typ = sym.type
            if call.name in ("count", "count_if", "approx_distinct"):
                cols.append(Column(jnp.zeros(8, typ.dtype), None, typ, None))
            else:
                cols.append(Column(jnp.zeros(8, typ.dtype),
                                   jnp.zeros(8, dtype=jnp.bool_), typ, None))
        return Page(tuple(cols), 1)

    def _exec_GroupIdNode(self, node: GroupIdNode) -> PageStream:
        src = self.execute(node.source)
        lay, typ = _layout(src.symbols)
        out_syms = node.outputs
        all_group = tuple(dict.fromkeys(
            s for gs in node.grouping_sets for s in gs))

        def gen():
            for page in src.iter_pages():
                for set_idx, gset in enumerate(node.grouping_sets):
                    in_set = {s.name for s in gset}
                    cols = []
                    for sym in all_group + node.passthrough:
                        c = page.column(lay[sym.name])
                        if sym in all_group and sym.name not in in_set:
                            # null out keys excluded from this grouping set
                            c = Column(c.values,
                                       jnp.zeros(page.capacity, jnp.bool_),
                                       c.type, c.dictionary)
                        cols.append(c)
                    gid = Column(
                        jnp.full(page.capacity, set_idx, dtype=jnp.int64),
                        None, T.BIGINT, None)
                    cols.append(gid)
                    yield Page(tuple(cols), page.num_rows)
        return PageStream(gen(), out_syms)

    def _exec_SortNode(self, node: SortNode) -> PageStream:
        src = self.execute(node.source)
        lay, _ = _layout(src.symbols)
        keys = [SortKey(lay[o.symbol.name], o.ascending, o.nulls_first)
                for o in node.order_by]

        sort_op = cached_kernel(("sort", tuple(keys)),
                                lambda: order_by(keys))

        def gen():
            # sort spill (spiller/ + MergingSortedPages analog, re-thought):
            # over-budget inputs flush to host RANGE partitions of the
            # leading sort key (ties and NULLs can't straddle partitions —
            # exec/spill.py leading_rank), then each partition re-stages,
            # fully sorts, and emits in partition order == global order.
            from trino_tpu.exec.memory import page_bytes
            from trino_tpu.exec.spill import (partition_by_range,
                                              rank_bounds, leading_rank)
            threshold = int(self.session.get("sort_spill_threshold_bytes"))
            npart = int(self.session.get("spill_partition_count"))
            spillable = bool(self.session.get("spill_enabled")) and keys
            k0 = keys[0]
            store = None
            bounds = None
            part_op = None
            buf: List[Page] = []
            buf_bytes = 0

            def flush():
                nonlocal store, bounds, part_op, buf, buf_bytes
                self._fault_site("spill", "sort")
                merged = self.merge_counted(buf)
                buf, buf_bytes = [], 0
                if merged is None:
                    return
                self._record_spill(page_bytes(merged))
                if bounds is None:
                    store = self._new_spill_store(npart)
                    nf = k0.resolved_nulls_first()
                    rank_op = cached_kernel(
                        ("sort-spill-rank", k0.channel, k0.ascending, nf),
                        lambda: leading_rank(k0.channel, k0.ascending, nf))
                    bounds_op = cached_kernel(
                        ("sort-spill-bounds", npart),
                        lambda: rank_bounds(npart))
                    part_op = cached_kernel(
                        ("sort-spill-part", k0.channel, k0.ascending, nf,
                         npart),
                        lambda: partition_by_range(k0.channel, k0.ascending,
                                                   nf, npart))
                    bounds = bounds_op(rank_op(merged), merged.row_mask(),
                                       merged.num_rows)
                sorted_pg, counts = part_op(merged, bounds)
                store.spill_partitioned(
                    sorted_pg, host_read(counts, "spill_counts"))

            try:
                for page in src.iter_pages():
                    self._checkpoint()
                    buf.append(page)
                    buf_bytes += page_bytes(page)
                    if spillable and buf_bytes >= threshold:
                        flush()

                if store is None:
                    page = self.merge_counted(buf)
                    if page is None:
                        return
                    from trino_tpu.exec.memory import page_bytes as _pb
                    self.memory.reserve(_pb(page), "collect",
                                        device=self.mem_device)
                    try:
                        yield sort_op(page)
                    finally:
                        self._free_collected(page)
                    return
                if buf:
                    flush()
                for p in range(npart):
                    nrows = store.partition_rows(p)
                    if nrows == 0:
                        continue
                    pg = store.restage(p, _next_pow2(max(nrows, 1)))
                    store.drop(p)
                    yield sort_op(pg)
            finally:
                if store is not None:
                    store.close()
        return PageStream(gen(), src.symbols)

    def _exec_TopNNode(self, node: TopNNode) -> PageStream:
        src = self.execute(node.source)
        lay, _ = _layout(src.symbols)
        keys = tuple(SortKey(lay[o.symbol.name], o.ascending,
                             o.nulls_first) for o in node.order_by)
        # masked fixed-capacity kernel (ops/sort.top_n_masked): the count
        # rides as a runtime operand through the chain's param slots, so
        # the jit key is COUNT-FREE — LIMIT 5 and LIMIT 500 of one shape
        # dispatch the same warm executable, exactly like a hoisted
        # literal (the warmup-manifest contract for LIMIT k families)
        count = np.int32(node.count)
        key = ("topn-masked", keys)

        def builder():
            fn = top_n_masked(keys)
            return lambda page, g: fn(page, g[0])
        # per-page partial top-n fused with the upstream chain
        slot = self._slot(node)
        partial_topn = compose_chain(
            src.pending + ((key, builder, (count,),
                            None if slot is None
                            else DeviceShareSlot(slot)),))
        merge_kernel = cached_kernel(key, lambda: top_n_masked(keys),
                                     params=(count,))

        def gen():
            # partial top-n per page bounds the concat size at
            # count * n_pages (GroupedTopN-builder analog)
            partials = [partial_topn(page) for page in src.pages]
            if not partials:
                return
            merged = partials[0]
            if len(partials) > 1:
                # its two reads (the counts, the live prefixes) are in it
                with observed_activity("page_concat"):
                    merged = concat_pages(partials)
            if int(host_read(merged.num_rows, "topn_rows")) == 0:
                return
            yield merge_kernel(merged, count)
        return PageStream(gen(), src.symbols)

    def _exec_JoinNode(self, node: JoinNode) -> PageStream:
        if node.kind == JoinKind.CROSS and not node.criteria:
            return self._exec_cross_join(node)
        if node.kind == JoinKind.RIGHT:
            # execute as LEFT with sides swapped, then restore column order
            # (the engine always probes with the preserved side; reference
            # reaches the same shape via LookupJoinOperatorFactory's
            # probe/build orientation)
            return self._exec_right_join(node)
        if node.kind == JoinKind.FULL:
            return self._exec_full_join(node)
        probe_stream = self.execute(node.left)
        build_stream = self.execute(node.right)
        # adaptive build collection (HashBuilderOperator's revoke-during-
        # build, re-thought): an INNER spillable build collects with
        # INCREMENTAL reservation — memory pressure mid-collect switches
        # to the streaming partitioned hybrid join (build pages partition
        # to host one at a time, never materialized whole), so an
        # underestimated build is a strategy switch, not an OOM cliff.
        # String keys ride the same handoff: the overflow path stages the
        # build host-side and rebases every page onto ONE union pool
        # before co-partitioning (_restage_string_build) — co-partition
        # hashing compares dictionary CODES, which only align under a
        # shared pool.
        build_iter = None
        if node.kind == JoinKind.INNER \
                and bool(self.session.get("spill_enabled")) \
                and int(self.session.get("spill_partition_count")) > 1:
            build_page, build_iter = \
                self._collect_build_resilient(build_stream)
        else:
            build_page = self._collect(build_stream)
        probe_lay, probe_typ = _layout(probe_stream.symbols)
        build_lay, _ = _layout(build_stream.symbols)
        probe_keys = [probe_lay[c.left.name] for c in node.criteria]
        build_keys = [build_lay[c.right.name] for c in node.criteria]
        # PruneJoinColumns: node.outputs may be a subset of left+right
        # (optimizer sets output_symbols) — emit only those channels, so
        # probe/build gathers skip dropped columns entirely
        out_symbols = node.outputs
        out_names = {s.name for s in out_symbols}
        probe_keep = tuple(i for i, s in enumerate(probe_stream.symbols)
                           if s.name in out_names)
        build_keep = tuple(i for i, s in enumerate(build_stream.symbols)
                           if s.name in out_names)
        join_kind = JoinType.INNER if node.kind == JoinKind.INNER \
            else JoinType.LEFT
        outer = join_kind != JoinType.INNER

        # residual non-equi filter evaluated over joined layout — valid for
        # INNER only (LEFT would wrongly drop null-extended rows; planner
        # rejects such plans). Hoisted like chain predicates: the kernel
        # keys below carry the canonical tree, the values ride per call.
        post_pred = None
        post_params = ()
        if node.filter is not None:
            if join_kind != JoinType.INNER:
                raise ExecutionError(
                    "non-inner join with residual filter not supported")
            lay, typ = _layout(out_symbols)
            post_pred, post_params = self._hoist(
                lower_expr(node.filter, lay, typ))

        def join_op(cap: int, mode: str = "search"):
            def build():
                op = hash_join(probe_keys, build_keys, join_kind,
                               output_capacity=cap, prepared=True,
                               lookup=mode, probe_out=probe_keep,
                               build_out=build_keep)
                if post_pred is None:
                    return lambda p, b, g: op(p, b)
                post_filter = compile_filter(post_pred)

                def run(p, b, g):
                    out, total = op(p, b)
                    return out.filter(post_filter(out, g)), total
                return run
            kernel = cached_kernel(
                (_composite("join", probe_keys, outer), tuple(probe_keys),
                 tuple(build_keys), join_kind,
                 cap, post_pred, mode, probe_keep, build_keep), build,
                params=post_params)
            return lambda p, b: kernel(p, b, post_params)

        n_probe_cols = len(probe_keep)

        def unique_ops(mode: str):
            probe_op = cached_kernel(
                (_composite("uprobe", probe_keys), tuple(probe_keys),
                 tuple(build_keys), mode,
                 probe_keep),
                lambda: unique_inner_probe(probe_keys, build_keys,
                                           lookup=mode,
                                           probe_out=probe_keep))

            def build_attach():
                from trino_tpu.ops.join import attach_build
                at = attach_build(n_probe_cols, build_out=build_keep)
                fn = None if post_pred is None else compile_filter(post_pred)

                def run(pre, prepared, g):
                    out = at(pre, prepared)
                    if fn is not None:
                        out = out.filter(fn(out, g))
                    return out
                return run
            attach_kernel = cached_kernel(
                ("uattach", n_probe_cols, post_pred, build_keep),
                build_attach, params=post_params)
            attach_op = lambda pre, prepared: attach_kernel(  # noqa: E731
                pre, prepared, post_params)
            return probe_op, attach_op

        def gen():
            if build_iter is not None:
                # the build overflowed its reservation mid-collect: the
                # streaming partitioned hybrid consumes the remaining
                # pages without ever materializing the whole side
                node_id = ("join",
                           tuple(c.left.name for c in node.criteria),
                           tuple(c.right.name for c in node.criteria))
                if any(T.is_string(build_stream.symbols[bk].type)
                       for bk in build_keys):
                    # string keys: stage the build host-side and rebase
                    # every page onto ONE union pool first — the
                    # co-partition hash and the per-partition kernels
                    # compare dictionary CODES, so both sides must share
                    # a pool before any partitioning happens. The probe
                    # then re-encodes onto that union pool exactly like
                    # the collected path's dictionary alignment (INNER
                    # only, which the overflow gates guarantee).
                    stage, pools = self._restage_string_build(
                        build_iter, build_keys)
                    if stage is None:
                        return      # empty build, INNER: no output rows
                    try:
                        aligned = self._align_probe_to_pools(
                            probe_stream,
                            {pk: pools[bk]
                             for pk, bk in zip(probe_keys, build_keys)
                             if bk in pools})
                        replay = stage.drain_partition_chunks(
                            0, stage.chunk_rows_for(0, self._spill_budget(
                                int(self.session.get(
                                    "join_spill_threshold_bytes")))))
                        yield from self._run_partitioned_inner(
                            aligned, replay, probe_keys, build_keys,
                            join_op, node_id=node_id)
                    finally:
                        stage.close()
                    return
                yield from self._run_partitioned_inner(
                    probe_stream, build_iter, probe_keys, build_keys,
                    join_op, node_id=node_id)
                return
            collected = build_page   # only the _collect'ed page was reserved
            bp = build_page
            if bp is None:
                if join_kind == JoinType.INNER:
                    return
                # LEFT join with empty build: emit null-extended probe rows
                bp = self._null_build_page(node.right.outputs)
            # INNER only: the sentinel codes for probe values absent from
            # the build pool are filtered out by inner semantics before
            # any decode; LEFT would emit them (out-of-pool codes in the
            # output), so mismatched-dictionary LEFT keys stay fail-loud
            # in the kernels
            aligned = probe_stream
            if join_kind == JoinType.INNER:
                aligned = self._align_join_dictionaries(
                    probe_stream, bp, probe_keys, build_keys)
            from trino_tpu.exec.memory import page_bytes
            if join_kind == JoinType.INNER and build_page is not None and \
                    self.session.get("spill_enabled") and \
                    page_bytes(build_page) > int(self.session.get(
                        "join_spill_threshold_bytes")):
                yield from self._run_spilled_inner(
                    aligned, build_page, probe_keys, build_keys,
                    post_pred, post_params, probe_keep, build_keep,
                    join_op,
                    skew_hint=getattr(node, "build_skew_estimate", None),
                    node_id=("join",
                             tuple(c.left.name for c in node.criteria),
                             tuple(c.right.name for c in node.criteria)))
                return
            try:
                prepared, max_run, mode = self._prepare_probe(
                    build_keys, bp, inner=not outer, outer=outer)
                prefilter = None
                if join_kind == JoinType.INNER and \
                        self.session.get("enable_dynamic_filtering") and \
                        not T.is_string(
                            probe_stream.symbols[probe_keys[0]].type):
                    # dynamic filtering: build-side key range -> probe-side
                    # scan prefilter (first join key bounds any composite)
                    from trino_tpu.ops.join import (build_key_bounds,
                                                    range_prefilter)
                    bounds_op = cached_kernel(
                        ("dfbounds", build_keys[0]),
                        lambda: build_key_bounds(build_keys))
                    pf_op = cached_kernel(
                        ("dfrange-mask", probe_keys[0]),
                        lambda: range_prefilter(probe_keys[0]))
                    prefilter = (pf_op, bounds_op(bp))
                    # the same build-side range, pushed into connector
                    # FILE/ROW-GROUP pruning when the probe key maps
                    # straight to a zone-mapped scan column (the lake's
                    # dynamic-filter pushdown) — the scan's generator
                    # has not been pulled yet (build-before-probe), so
                    # the domain lands before splits are chosen
                    target = self._dyn_scan_target(
                        node.left,
                        probe_stream.symbols[probe_keys[0]].name)
                    if target is not None:
                        scan_node, col_name, col_type = target
                        lo_h, hi_h = host_read(prefilter[1], "build_key_range")
                        self.register_dynamic_domain(
                            scan_node, col_name, col_type,
                            lo_h.item(), hi_h.item())
                probe_in = self._lookup_lanes(self._coalesce_stream(
                    aligned, prefilter=prefilter))
                if join_kind == JoinType.INNER and max_run <= 1:
                    # unique build side (primary/dimension key): the
                    # no-expansion probe (against the table of build rows
                    # `_prepare_probe` made for it, where the keys are
                    # dense) + live-size build attach
                    probe_op, attach_op = unique_ops(mode)
                    yield from self._run_unique_inner(
                        probe_in, prepared, probe_op, attach_op)
                else:
                    yield from _run_with_overflow(
                        probe_in, prepared,
                        lambda cap: join_op(cap, mode),
                        self.page_capacity)
            finally:
                self._free_collected(collected)
        return PageStream(gen(), out_symbols)

    def _run_spilled_inner(self, probe_stream, build_page,
                           probe_keys, build_keys, post_pred, post_params,
                           probe_keep, build_keep,
                           fallback_join_op, skew_hint=None,
                           node_id=None) -> Iterator[Page]:
        """Spill-mode INNER join (HashBuilderOperator spill states +
        SpillingJoinProcessor analog): sort the build keys on device, move
        the build's payload columns to HOST RAM, keep only (sorted keys,
        permutation) in HBM (~12B/row), probe streams against the key
        array, and gather build columns host-side at match count.

        Duplicate-key and string-keyed builds — the shapes the unique
        key-array probe cannot serve — route to the robust dynamic
        HYBRID partitioned join (`_run_partitioned_inner`): both sides
        hash-partition to host, partitions join in memory, over-budget
        partitions recursively repartition, heavy keys split out. The
        CBO's `build_skew_estimate` (> 2 expected duplicates per key)
        pre-routes there without paying a wasted unique-prep; the
        runtime observation still decides when the estimate is absent
        or wrong."""
        from trino_tpu.exec.memory import page_bytes
        from trino_tpu.ops.join import (attach_build_host,
                                        build_dense_table,
                                        prepare_build_spilled,
                                        spilled_dense_probe,
                                        spilled_unique_probe)
        self._fault_site("spill", "join-build")
        npart = int(self.session.get("spill_partition_count"))
        partitioned_ok = npart > 1
        # varchar join keys compare by per-dictionary code — the spilled
        # probe never sees the build dictionaries, so it cannot apply the
        # shared-dictionary guard the in-memory kernels enforce; the
        # partitioned path restages full pages (dictionaries ride along
        # in store meta) and runs the verifying in-memory kernels per
        # partition, so string keys go there too
        string_keyed = any(
            build_page.columns[bk].dictionary is not None
            for bk in build_keys)
        is_unique = False
        cbo_partitioned = (partitioned_ok and skew_hint is not None
                           and skew_hint > 2.0)
        if not string_keyed and not cbo_partitioned:
            try:
                prep = cached_kernel(
                    ("spill-prep", tuple(build_keys)),
                    lambda: prepare_build_spilled(build_keys))
                (bkey_s, bperm, n_live, n_rows_d, has_null, is_unique_d,
                 kmin_d, kmax_d) = prep(build_page)
                # ONE batched fetch for all four scalars (each fetch is a
                # device sync)
                uq, nr, km, kx = host_read(
                    [is_unique_d, n_rows_d, kmin_d, kmax_d], "build_key_stats")
                is_unique, n_rows, kmin, kmax = \
                    bool(uq), int(nr), int(km), int(kx)
            except Exception:
                self._free_collected(build_page)
                raise
        if string_keyed or cbo_partitioned or not is_unique:
            if partitioned_ok:
                yield from self._run_partitioned_inner(
                    probe_stream, build_page, probe_keys, build_keys,
                    fallback_join_op, node_id=node_id)
                return
            # partitioning disabled (spill_partition_count <= 1):
            # legacy in-memory expansion join
            try:
                prepared, _max_run, mode = self._prepare_probe(
                    build_keys, build_page)
                yield from _run_with_overflow(
                    self._lookup_lanes(self._coalesce_stream(probe_stream)),
                    prepared, lambda cap: fallback_join_op(cap, mode),
                    self.page_capacity)
            finally:
                self._free_collected(build_page)
            return
        # pruned layouts: the pre page carries kept probe cols (plus
        # verify-only key cols for composite keys, dropped after attach);
        # only kept build cols move to host for emission, key cols ride
        # along host-side when composite verification needs them
        composite = len(probe_keys) > 1
        probe_out = list(probe_keep)
        extra_p = [k for k in probe_keys if k not in probe_out] \
            if composite else []
        probe_out_full = tuple(probe_out + extra_p)
        n_pre_cols = len(probe_out_full)
        host_idx = list(build_keep) + \
            ([k for k in build_keys if k not in build_keep]
             if composite else [])
        emit = tuple(range(len(build_keep)))
        verify = None
        if composite:
            verify = [(probe_out_full.index(pk), host_idx.index(bk))
                      for pk, bk in zip(probe_keys, build_keys)]
        # move payload columns to host CHUNK-WISE (round 15, the PR 10
        # leftover): the old whole-build device_get sliced every column
        # up front, transiently materializing a second copy of a build
        # that is over the spill threshold BY DEFINITION — at exactly
        # the moment HBM is scarce. Each chunk's device slice is now
        # the only transient, reserved against the ledger while it
        # transfers.
        try:
            host_cols = [
                self._stage_column_host(build_page.columns[ci], n_rows)
                for ci in host_idx]
        except Exception:
            self._free_collected(build_page)
            raise
        self._record_spill(sum(
            v.nbytes + (m.nbytes if m is not None else 0)
            for v, m, _, _ in host_cols))
        self._free_collected(build_page)
        # dense spilled builds (surrogate keys, the common >threshold
        # case): ONE int32 row table on device — ~4B/slot instead of
        # 12B/row, and probes are one gather instead of anchored search
        span = kmax - kmin + 1 if kmax >= kmin else 0
        spill_dense = 0 < span <= (1 << 28)
        if spill_dense:
            size = _next_pow2(span)
            tab_op = cached_kernel(("dense-table-rows", size),
                                   lambda: build_dense_table(size))
            table = tab_op(bkey_s, n_live, kmin, bperm)
            kmin_dev = jnp.uint64(kmin)
            bkey_s = bperm = None   # free sorted keys + permutation
            held_bytes = int(table.nbytes)
            probe_op = cached_kernel(
                ("spill-probe-dense", tuple(probe_keys), probe_out_full),
                lambda: spilled_dense_probe(probe_keys,
                                            probe_out=probe_out_full))
        else:
            held_bytes = int(bkey_s.nbytes + bperm.nbytes)
            probe_op = cached_kernel(
                ("spill-probe", tuple(probe_keys), probe_out_full),
                lambda: spilled_unique_probe(probe_keys,
                                             probe_out=probe_out_full))
        self.memory.reserve(held_bytes, "join-spill-keys",
                            device=self.mem_device)
        post_filter = None if post_pred is None else \
            compile_filter(post_pred)   # called with post_params below
        drop_extra = None
        if extra_p:
            drop_extra = tuple(range(len(probe_keep))) + tuple(
                range(n_pre_cols, n_pre_cols + len(build_keep)))
        try:
            it2 = probe_stream if isinstance(probe_stream, Iterator) \
                else self._coalesce_stream(probe_stream).iter_pages()
            self._count_lookup("row_table" if spill_dense else "search")
            for batch in _byte_bounded_batches(self._lookup_lanes(it2),
                                               1 << 29):
                if spill_dense:
                    results = [probe_op(p, table, kmin_dev) for p in batch]
                else:
                    results = [probe_op(p, bkey_s, bperm, n_live)
                               for p in batch]
                fetched = host_read(
                    [(t, pre.num_rows) for pre, _, t in results],
                    "probe_totals")
                for (pre, found, _), (total, live) in zip(results, fetched):
                    total, live = int(total), int(live)
                    if total == 0:
                        continue
                    pre = self._compact_counted(pre, found, total, live)
                    pre = self._tight(pre, total)
                    out = attach_build_host(pre, n_pre_cols, host_cols,
                                            verify=verify, emit=emit)
                    if drop_extra is not None:
                        out = out.select_columns(drop_extra)
                    if post_filter is not None:
                        out = out.filter(post_filter(out, post_params))
                    yield out
        finally:
            self.memory.free(held_bytes, "join-spill-keys",
                             device=self.mem_device)

    # device-transient budget for staging one spilled-build column chunk
    _SPILL_STAGE_CHUNK_BYTES = 128 << 20

    def _stage_column_host(self, c, n_rows: int):
        """One build payload column staged to host numpy in BOUNDED
        chunks: the device transient is a single chunk's slice (reserved
        against the query ledger for the duration of its transfer), not
        a full second copy of the column. Returns the
        (values, valid, type, dictionary) tuple attach_build_host
        consumes."""
        n = max(n_rows, 1)
        width = int(np.dtype(c.values.dtype).itemsize) \
            + (1 if c.valid is not None else 0)
        chunk = max(1 << 16, self._SPILL_STAGE_CHUNK_BYTES
                    // max(width, 1))
        vals = np.empty(n, dtype=np.dtype(c.values.dtype))
        valid = None if c.valid is None else np.empty(n, dtype=bool)
        off = 0
        while off < n:
            hi = min(off + chunk, n)
            held = (hi - off) * width
            self.memory.reserve(held, "spill-stage",
                                device=self.mem_device)
            try:
                self._checkpoint()
                vals[off:hi] = np.asarray(host_read(
                    c.values[off:hi], "stage_column"))
                if valid is not None:
                    valid[off:hi] = np.asarray(host_read(
                        c.valid[off:hi], "stage_column"))
            finally:
                self.memory.free(held, "spill-stage",
                                 device=self.mem_device)
            off = hi
        return vals, valid, c.type, c.dictionary

    def _collect_build_resilient(self, stream: PageStream):
        """Collect a join build side with INCREMENTAL reservation: each
        page reserves before the next materializes, so memory pressure
        surfaces mid-collect — where it is a STRATEGY SWITCH (return the
        pages-so-far chained with the rest of the stream for the
        streaming partitioned join) instead of a terminal OOM after the
        whole side sat in HBM. Returns (page, None) when the build fit
        (classic paths, reservation swapped to the merged page) or
        (None, iterator) on pressure; (None, None) = empty build."""
        from trino_tpu.exec.memory import (ClusterOutOfMemoryError,
                                           ExceededMemoryLimitError,
                                           page_bytes)
        self._fault_site("memory", "collect")
        pages: List[Page] = []
        held = 0
        it = stream.iter_pages()
        try:
            for page in it:
                self._checkpoint()
                b = page_bytes(page)
                try:
                    self.memory.reserve(b, "collect",
                                        device=self.mem_device)
                except (ExceededMemoryLimitError,
                        ClusterOutOfMemoryError):
                    # hand every held byte back (a killer victim's
                    # release) and clear a self-kill mark: the pressure
                    # is relieved by NOT materializing this build
                    self.memory.free(held, "collect",
                                     device=self.mem_device)
                    self.memory.clear_kill()
                    self._adaptive_span("join-build-overflow",
                                        held_bytes=held + b)
                    pages.append(page)
                    return None, _drain_then(pages, it)
                held += b
                pages.append(page)
        except BaseException:
            self.memory.free(held, "collect", device=self.mem_device)
            raise
        merged = self.merge_counted(pages)
        # swap the per-page reservations for the merged page's bytes
        # (merge shrinks to the live pow2): free FIRST — holding both
        # transiently would double-reserve and trip a limit the merged
        # page alone fits under
        self.memory.free(held, "collect", device=self.mem_device)
        if merged is None:
            return None, None
        try:
            self.memory.reserve(page_bytes(merged), "collect",
                                device=self.mem_device)
        except (ExceededMemoryLimitError, ClusterOutOfMemoryError):
            # even the merged page is over the line: degrade with it as
            # the (single-page) streaming build
            self.memory.clear_kill()
            self._adaptive_span("join-build-overflow",
                                held_bytes=page_bytes(merged))
            return None, iter([merged])
        return merged, None

    def _spill_budget(self, threshold: int) -> int:
        """The per-partition device budget for restaging/recursion
        decisions: the configured spill threshold, shrunk under an
        active memory limit so a restaged partition's reservation can
        always be granted (a budget above the limit would turn the
        ladder's graceful degradation back into a reservation
        failure)."""
        budget = int(threshold)
        limit = getattr(self.memory, "limit", None)
        if limit:
            budget = min(budget, max(int(limit) // 4, 1 << 16))
        pool = getattr(self.memory, "pool", None)
        if pool is not None and pool.limit:
            budget = min(budget, max(int(pool.limit) // 4, 1 << 16))
        return max(budget, 1)

    def _run_partitioned_inner(self, probe_stream, build_source,
                               probe_keys, build_keys, join_op,
                               node_id=None) -> Iterator[Page]:
        """Robust dynamic hybrid hash join for duplicate-key / skewed /
        string-keyed over-threshold builds (the shapes that previously
        fell back to an UNBOUNDED in-memory build): both sides
        hash-partition into host stores with one device partition-sort
        each, then every co-partition joins with the normal in-memory
        kernels when its build fits the spill budget — and degrades
        gracefully when it doesn't (`_join_partitions`: salted recursive
        repartition, heavy-key splitting, bounded chunked-build
        fallback). No cliff: device footprint is bounded by one
        partition's build plus one probe chunk at every depth."""
        from trino_tpu.exec.memory import page_bytes
        from trino_tpu.exec.spill import partition_by_hash
        npart = int(self.session.get("spill_partition_count"))
        threshold = self._spill_budget(
            int(self.session.get("join_spill_threshold_bytes")))
        bkeys_t, pkeys_t = tuple(build_keys), tuple(probe_keys)
        build_is_page = isinstance(build_source, Page)

        def part_op(keys, salt):
            return cached_kernel(
                ("join-spill-part", keys, npart, salt),
                lambda: partition_by_hash(keys, npart, salt=salt))

        try:
            bstore = self._new_spill_store(npart)
            pstore = self._new_spill_store(npart)
        except BaseException:
            if build_is_page:
                self._free_collected(build_source)
            raise
        try:
            self._fault_site("spill", "join-part")
            bop = part_op(bkeys_t, 0)
            if build_is_page:
                self._record_spill(page_bytes(build_source))
                try:
                    sorted_pg, counts = bop(build_source)
                    bstore.spill_partitioned(sorted_pg,
                                             host_read(counts, "spill_counts"))
                finally:
                    self._free_collected(build_source)
            else:
                # streaming build (mid-collect overflow handoff): pages
                # partition to host one at a time — the whole side is
                # never resident on device
                for bpage in build_source:
                    self._checkpoint()
                    sorted_pg, counts = bop(bpage)
                    bstore.spill_partitioned(sorted_pg,
                                             host_read(counts, "spill_counts"))
                self._record_spill(bstore.bytes)
            it = probe_stream if isinstance(probe_stream, Iterator) \
                else self._coalesce_stream(probe_stream).iter_pages()
            pop = part_op(pkeys_t, 0)
            for page in it:
                self._checkpoint()
                sorted_pg, counts = pop(page)
                pstore.spill_partitioned(sorted_pg,
                                         host_read(counts, "spill_counts"))
            self._record_spill(pstore.bytes)
            yield from self._join_partitions(
                bstore, pstore, 0, bkeys_t, pkeys_t, join_op, part_op,
                threshold, node_id)
        finally:
            bstore.close()
            pstore.close()

    def _join_partitions(self, bstore, pstore, depth: int, bkeys, pkeys,
                         join_op, part_op, threshold: int,
                         node_id=None) -> Iterator[Page]:
        """One round of the hybrid join over co-partitioned stores. Per
        partition, in order: in-budget -> in-memory join; heavy build
        keys (unsplittable by ANY re-hash) -> split both sides out into
        the dedicated chunked-build pass (the replicate/spread analog of
        parallel/exchange's JSPIM handling: build chunks replicate, the
        probe partition streams — spreads — through each); still over
        budget -> recursive salted repartition of BOTH sides up to
        `spill_max_recursion`; at max depth -> bounded chunked-build
        fallback. Every switch counts and spans."""
        from trino_tpu.exec.spill import (detect_partition_heavy_keys,
                                          partition_key_hashes,
                                          split_partition)
        max_rec = int(self.session.get("spill_max_recursion"))
        heavy_limit = int(self.session.get("spill_heavy_key_limit"))
        npart = bstore.npart
        for p in range(npart):
            self._checkpoint()
            brows = bstore.partition_rows(p)
            prows = pstore.partition_rows(p)
            if brows == 0 or prows == 0:
                bstore.drop(p)
                pstore.drop(p)
                continue
            if bstore.partition_bytes(p) <= max(threshold, 1):
                yield from self._join_one_partition(
                    bstore, pstore, p, bkeys, join_op, threshold)
                continue
            if heavy_limit > 0 and depth < max_rec and npart > 1:
                bhashes = partition_key_hashes(bstore, p, bkeys)
                heavy = detect_partition_heavy_keys(
                    bstore, p, bkeys, heavy_limit,
                    max(2, brows // (2 * max(npart, 2))),
                    piece_hashes=bhashes)
                if len(heavy):
                    self._fault_site("spill", "join-heavy")
                    self._adaptive_event("heavy_key_splits")
                    self._adaptive_span("join-heavy-split", depth=depth,
                                        keys=int(len(heavy)))
                    if self.adaptive is not None and node_id is not None:
                        self.adaptive.record_join_heavy(node_id, heavy)
                    hb = split_partition(bstore, p, bkeys, heavy,
                                         piece_hashes=bhashes)
                    hp = split_partition(pstore, p, pkeys, heavy)
                    try:
                        yield from self._join_chunked_build(
                            hb, hp, 0, bkeys, join_op, threshold)
                    finally:
                        hb.close()
                        hp.close()
                    if bstore.partition_rows(p) == 0 or \
                            pstore.partition_rows(p) == 0:
                        bstore.drop(p)
                        pstore.drop(p)
                        continue
                    if bstore.partition_bytes(p) <= max(threshold, 1):
                        yield from self._join_one_partition(
                            bstore, pstore, p, bkeys, join_op, threshold)
                        continue
            if depth >= max_rec or npart <= 1:
                self._fault_site("spill", "join-fallback")
                self._adaptive_event("spill_fallbacks")
                self._adaptive_span("join-spill-fallback", depth=depth)
                yield from self._join_chunked_build(
                    bstore, pstore, p, bkeys, join_op, threshold)
                continue
            self._fault_site("spill", "join-recurse")
            self._adaptive_event("join_recursions")
            self._adaptive_span("join-spill-recurse", depth=depth + 1)
            childb = self._new_spill_store(npart)
            childp = self._new_spill_store(npart)
            try:
                bop = part_op(bkeys, depth + 1)
                # drain both transfers: the recursion must never hold
                # parent AND child copies of one side against the budget
                for chunk in bstore.drain_partition_chunks(
                        p, bstore.chunk_rows_for(p, threshold)):
                    self._checkpoint()
                    spg, cnt = bop(chunk)
                    childb.spill_partitioned(
                        spg, host_read(cnt, "spill_counts"))
                bstore.drop(p)
                pop = part_op(pkeys, depth + 1)
                for chunk in pstore.drain_partition_chunks(
                        p, pstore.chunk_rows_for(p, threshold)):
                    self._checkpoint()
                    spg, cnt = pop(chunk)
                    childp.spill_partitioned(
                        spg, host_read(cnt, "spill_counts"))
                pstore.drop(p)
                yield from self._join_partitions(
                    childb, childp, depth + 1, bkeys, pkeys, join_op,
                    part_op, threshold, node_id)
            finally:
                childb.close()
                childp.close()

    def _join_one_partition(self, bstore, pstore, p: int, bkeys,
                            join_op, threshold: int) -> Iterator[Page]:
        """In-memory join of one co-partition: restage the build side
        (reserved against the query ledger), prepare once, stream the
        probe partition through in bounded chunks."""
        from trino_tpu.exec.memory import page_bytes
        nrows = bstore.partition_rows(p)
        bpage = bstore.restage(p, _next_pow2(max(nrows, 1)))
        bstore.drop(p)
        held = page_bytes(bpage)
        self.memory.reserve(held, "join-part-build",
                            device=self.mem_device)
        try:
            prepared, _max_run, mode = self._prepare_probe(
                list(bkeys), bpage)
            yield from _run_with_overflow(
                self._lookup_lanes(pstore.drain_partition_chunks(
                    p, pstore.chunk_rows_for(p, threshold))),
                prepared,
                lambda cap: join_op(cap, mode),
                self.page_capacity)
            pstore.drop(p)
        finally:
            self.memory.free(held, "join-part-build",
                             device=self.mem_device)

    def _join_chunked_build(self, bstore, pstore, p: int, bkeys,
                            join_op, threshold: int) -> Iterator[Page]:
        """Bounded chunked-build join: INNER join distributes over
        DISJOINT build chunks (each probe row meets each of its key's
        build rows in exactly one chunk), so joining the probe partition
        against budget-sized build chunks is correct at ANY build size —
        the bounded-memory floor under both the heavy-key path and the
        max-recursion fallback. More passes, never more memory."""
        from trino_tpu.exec.memory import page_bytes
        pchunk_rows = pstore.chunk_rows_for(p, threshold)
        # build chunks drain (single pass); the probe partition must
        # stay resident — it re-streams once per build chunk
        for bchunk in bstore.drain_partition_chunks(
                p, bstore.chunk_rows_for(p, threshold)):
            self._checkpoint()
            held = page_bytes(bchunk)
            self.memory.reserve(held, "join-chunk-build",
                                device=self.mem_device)
            try:
                prepared, _mr, mode = self._prepare_probe(
                    list(bkeys), bchunk)
                yield from _run_with_overflow(
                    self._lookup_lanes(
                        pstore.iter_partition_chunks(p, pchunk_rows)),
                    prepared,
                    lambda cap, m=mode: join_op(cap, m),
                    self.page_capacity)
            finally:
                self.memory.free(held, "join-chunk-build",
                                 device=self.mem_device)
        bstore.drop(p)
        pstore.drop(p)

    def _compact_counted(self, page: Page, mask, kept: int, live: int,
                         tag: str = "probe-compact") -> Page:
        """Compact a page of the probe path to the rows `mask` keeps, the
        host holding their count `kept` and the page's live count `live`
        — the second of two steps; the first (a lookup, a range mask)
        made `mask` and both counts and moved nothing. By what the counts
        show, with `_tight`'s rule:

        - kept == live: nothing was dropped, nothing moves (fact-to-dim
          joins often match every row);
        - `_tight_capacity` puts `kept` rows on a rung below the page:
          `Page.compact_to` at that rung — the kept prefix alone is
          gathered, not the lanes `_tight` would slice off next (q3's
          second join keeps 0.5 % of a 16-32 M-lane buffer; a gather
          costs by the index, PERF.md PR 31);
        - otherwise one full-capacity `Page.filter`.

        The result has the capacity `_tight(page.filter(mask), kept)`
        would have, so what follows (attach, coalesce) compiles for the
        same pow2 rungs either way. Counted on the query's collector as
        `probe_compactions_skipped` / `_tight` / `_full`."""
        rung = self._tight_capacity(page, kept)
        if kept == live:
            gathered, out = 0, page
        elif rung < page.capacity:
            op = cached_kernel((tag, rung), lambda: lambda p, m:
                               p.compact_to(m, rung))
            gathered, out = rung, op(page, mask)
        else:
            op = cached_kernel((tag,), lambda: lambda p, m: p.filter(m))
            gathered, out = rung, op(page, mask)
        if self.collector is not None:
            self.collector.count_probe_compaction(page.capacity, gathered)
        return out

    def _run_unique_inner(self, probe_stream, prepared, probe_op,
                          attach_op) -> Iterator[Page]:
        """Drive the unique-build INNER fast path in two steps with the
        host's counts between them: the probe kernel per page (ONE gather
        a lane against `prepared[10]`, the table of build rows, where
        `_prepare_probe` routed `dense`; a searchsorted where it did not)
        yields the match mask and count and moves nothing; one batched
        fetch of (matched, live) per batch; then `_compact_counted` per
        buffer (skip / matched prefix at its pow2 rung / full) and the
        build columns gathered at that rung — so neither the compaction
        nor the attach runs at probe capacity. No overflow loop: output
        rows <= probe rows always."""
        it = probe_stream if isinstance(probe_stream, Iterator) \
            else probe_stream.iter_pages()
        for batch in _byte_bounded_batches(it, 1 << 29):
            results = [probe_op(page, prepared) for page in batch]
            fetched = host_read(
                [(t, pre.num_rows) for pre, _, t in results], "probe_totals")
            for (pre, found, _), (total, live) in zip(results, fetched):
                total, live = int(total), int(live)
                if total == 0:
                    continue
                out = self._compact_counted(pre, found, total, live)
                yield attach_op(self._tight(out, total), prepared)

    def _align_join_dictionaries(self, probe_stream: PageStream,
                                 build_page: Page, probe_keys,
                                 build_keys) -> PageStream:
        """String join keys across DISTINCT dictionaries: remap probe key
        codes onto the build side's pool (DictionaryBlock re-encode; the
        kernels compare codes, so both sides must share one pool)."""
        return self._align_probe_to_pools(
            probe_stream,
            {pk: build_page.columns[bk].dictionary
             for pk, bk in zip(probe_keys, build_keys)
             if build_page.columns[bk].dictionary is not None})

    def _restage_string_build(self, build_source, build_keys):
        """Overflow handoff for STRING-keyed builds (closes the gap the
        streaming partitioned join carried since it landed): pages of a
        streaming build may encode the same key column against DISTINCT
        pools (per-source dictionaries under a union, re-created memory
        tables), and co-partition hashing compares CODES — so the whole
        build stages host-side FIRST (single-partition store: one device
        compaction per page, the side is never resident whole), then
        every dictionary column whose pieces span more than one pool is
        rebased onto the union pool with a host-side int32 code remap
        (DictionaryBlock 'compact to shared pool', applied at rest).

        Returns (stage, {build_channel: dictionary}) — the caller drains
        partition 0 as the replay build source, aligns the probe to the
        returned pools BEFORE co-partitioning, and owns stage.close().
        (None, {}) = empty build."""
        from trino_tpu.exec.spill import partition_by_hash
        from trino_tpu.page import union_dictionaries
        bkeys_t = tuple(build_keys)
        compact = cached_kernel(
            ("join-spill-part", bkeys_t, 1, 0),
            lambda: partition_by_hash(bkeys_t, 1, salt=0))
        stage = self._new_spill_store(1)
        try:
            piece_dicts: List[list] = []
            for page in build_source:
                self._checkpoint()
                self._fault_site("spill", "join-string-stage")
                sorted_pg, counts = compact(page)
                before = len(stage.pieces[0])
                stage.spill_partitioned(sorted_pg,
                                        host_read(counts, "spill_counts"))
                if len(stage.pieces[0]) > before:
                    # dictionaries per APPENDED piece (all-pad pages
                    # append nothing) — stage.meta only remembers the
                    # first page's pools
                    piece_dicts.append(
                        [c.dictionary for c in page.columns])
            self._record_spill(stage.bytes)
            if stage.meta is None:
                stage.close()
                return None, {}
            for ci in range(len(stage.meta)):
                dicts = [pd[ci] for pd in piece_dicts]
                if dicts[0] is None:
                    continue
                uniq: List = []
                for d in dicts:
                    if not any(d is u or d.fingerprint == u.fingerprint
                               for u in uniq):
                        uniq.append(d)
                final = uniq[0]
                if len(uniq) > 1:
                    self._adaptive_span("join-string-pool-union",
                                        channel=ci, pools=len(uniq))
                    union, remaps = union_dictionaries(uniq)
                    by_fp = {u.fingerprint: np.asarray(r)
                             for u, r in zip(uniq, remaps)}
                    for piece, d in zip(stage.pieces[0], dicts):
                        tbl = by_fp[d.fingerprint]
                        vals = piece[ci][0]
                        # padding/null codes (< 0) pass through; live
                        # codes remap. int32 -> int32: the store's byte
                        # accounting is unchanged by the rewrite.
                        piece[ci] = (np.where(
                            vals >= 0,
                            tbl[np.clip(vals, 0, len(tbl) - 1)],
                            vals).astype(vals.dtype), piece[ci][1])
                    final = union
                typ, _ = stage.meta[ci]
                stage.meta[ci] = (typ, final)
            pools = {bk: stage.meta[bk][1] for bk in bkeys_t
                     if stage.meta[bk][1] is not None}
            return stage, pools
        except BaseException:
            stage.close()
            raise

    def _align_probe_to_pools(self, probe_stream: PageStream, pools
                              ) -> PageStream:
        """Re-encode probe key channels onto given build-side pools
        (`pools`: {probe_channel: build Dictionary}). Probe values absent
        from the build pool map to unique sentinels past the pool end —
        they can never match (INNER-only discipline; LEFT keeps the
        fail-loud kernels). Lazy: tables build on the first page per
        (probe-dict, channel) pair."""
        pools = {pk: bd for pk, bd in pools.items() if bd is not None}
        if not pools:
            return probe_stream
        maps: Dict[tuple, jnp.ndarray] = {}

        def gen():
            for page in probe_stream.iter_pages():
                cols = list(page.columns)
                changed = False
                for pk, bd in pools.items():
                    pc = cols[pk]
                    if pc.dictionary is None or pc.dictionary is bd:
                        continue
                    key = (id(pc.dictionary), pk)
                    tbl = maps.get(key)
                    if tbl is None:
                        pvals = pc.dictionary.values
                        n_b = len(bd.values)
                        if n_b:
                            codes = np.minimum(
                                np.searchsorted(bd.values, pvals),
                                n_b - 1).astype(np.int64)
                            present = bd.values[codes] == pvals
                        else:
                            codes = np.zeros(len(pvals), np.int64)
                            present = np.zeros(len(pvals), bool)
                        out = np.where(
                            present, codes,
                            n_b + np.arange(len(pvals), dtype=np.int64))
                        tbl = maps[key] = jnp.asarray(
                            out.astype(np.int32))
                    cols[pk] = Column(
                        jnp.take(tbl, jnp.clip(pc.values, 0),
                                 mode="clip"),
                        pc.valid, pc.type, bd)
                    changed = True
                yield Page(tuple(cols), page.num_rows) if changed else page
        return PageStream(gen(), probe_stream.symbols)

    def _prepare_build(self, build_keys, build_page, semi: bool = False,
                       outer: bool = False):
        """Sort the build side ONCE per join (LookupSourceFactory analog) —
        probe-page kernels consume the prepared tuple without re-sorting.
        `semi`: a semi, anti or mark join's; `outer`: a LEFT or FULL
        join's — each a program of its own name."""
        prep = cached_kernel(
            ("semijoin-prep" if semi
             else _composite("join-prep", build_keys, outer),
             tuple(build_keys)),
            lambda: prepare_build(build_keys, semi))
        return prep(build_page)

    # direct-address tables: pow2 sizes bound compile-shape diversity; the
    # slot cap bounds HBM (64M slots = 256MB int32 for in-memory builds)
    _DENSE_MAX_SLOTS = 1 << 26

    def _prepare_probe(self, build_keys, build_page, semi: bool = False,
                       inner: bool = False, outer: bool = False):
        """prepare_build + the ONE probe-lookup decision of every join,
        in memory or spilled: fetch (max_run, kmin, kmax) in one round
        trip; when the live-key span is small (dense surrogate keys —
        every TPC-H/DS join), append a direct-address lookup table so
        probe kernels cost one gather a lane ('dense') instead of a
        sort-engine searchsorted pass per buffer ('search': four sorts,
        two scatters and three gathers on the v5e).

        What a slot of the table holds follows from what was just read
        and from the join's kind, nothing to set. `inner` says the caller
        takes the no-expansion probe when the build turns out unique
        (INNER, `max_run <= 1`: unique_inner_probe): the table then
        holds the build ROW of each key and the probe is that one gather
        and nothing else. Every other consumer (hash_join over
        duplicates, LEFT/FULL, SEMI/ANTI/MARK) reads run_len at the
        key's sorted POSITION and keeps the position table. Counted on
        the query's collector as `probe_lookups_row_table` /
        `_position_table` / `_set_table` / `_search`. `outer` (a LEFT
        join's build) only names the programs.

        `semi` (SEMI, ANTI, MARK) on ONE key column decides BEFORE it
        sorts: such a join asks whether a key is there, not where, so
        its bounds come from a pass of reductions over the page as it
        was collected (semi_build_stats, the same one round trip), and
        a span the position table's rule admits gets the set table —
        one scatter from the unsorted lanes, lookup 'set', the
        prepared tuple set_semi_join's (the build page is not in it:
        the caller may free it) — where any other span sorts as before
        for `search`. A composite key is mix-hashed and verifies its
        candidates through the sort permutation: it takes the path it
        always took. `semi_build_lanes_set` / `_sorted` sum the build
        pages' capacities by which way they went.

        Returns (prepared [+ table], max_run, lookup); a semi join
        reads no max_run and gets None."""
        from trino_tpu.ops.join import (build_dense_table, build_set_table,
                                        semi_build_stats)
        # the position table keeps the fill rule it had (at most 4 slots
        # a build lane): no chip run has timed it
        fill_limit = min(max(4 * build_page.capacity, 1 << 20),
                         self._DENSE_MAX_SLOTS)
        if semi and len(build_keys) == 1:
            stats_op = cached_kernel(
                ("semijoin-stats", tuple(build_keys)),
                lambda: semi_build_stats(build_keys))
            kmin_dev, kmax_dev, n_rows, has_null = stats_op(build_page)
            kmin, kmax = (int(x) for x in host_read(
                [kmin_dev, kmax_dev], "build_key_stats"))
            span = kmax - kmin + 1 if kmax >= kmin else 0
            if not 0 < span <= fill_limit:
                self._adaptive_event("semi_build_lanes_sorted",
                                     build_page.capacity)
                self._count_lookup("search")
                return self._prepare_build(build_keys, build_page,
                                           semi), None, "search"
            size = _next_pow2(span)
            table_op = cached_kernel(
                ("semijoin-set-table", tuple(build_keys), size),
                lambda: build_set_table(build_keys, size))
            table, key_cols = table_op(build_page, kmin_dev)
            self._adaptive_event("semi_build_lanes_set",
                                 build_page.capacity)
            self._count_lookup("set_table")
            return (table, kmin_dev, n_rows, has_null, key_cols), None, \
                "set"
        prepared = self._prepare_build(build_keys, build_page, semi, outer)
        max_run, kmin, kmax = (int(x) for x in host_read(
            [prepared[7], prepared[8], prepared[9]], "build_key_stats"))
        if semi:
            self._adaptive_event("semi_build_lanes_sorted",
                                 build_page.capacity)
        rows = inner and max_run <= 1
        span = kmax - kmin + 1 if kmax >= kmin else 0
        # the row table is worth its slots at any fill: one gather runs
        # at 115-137 M lanes/s into a table of 2^24 slots holding 1.5 M
        # keys or a hundred, the search at 15-20 M, and the table's own
        # scatter is 1-21 ms (chip, PERF.md PR 38) — so only the slot cap
        # bounds it
        limit = self._DENSE_MAX_SLOTS if rows else fill_limit
        if not 0 < span <= limit:
            self._count_lookup("search")
            return prepared, max_run, "search"
        size = _next_pow2(span)
        tag = "semijoin-dense-table" if semi else \
            "dense-table-rows" if rows else \
            "dense-table-outer" if outer else "dense-table"
        table_op = cached_kernel(
            (tag, size), lambda: build_dense_table(size, semi))
        table = table_op(prepared[1], prepared[3], prepared[8],
                         prepared[2] if rows else None)
        self._count_lookup("row_table" if rows else "position_table")
        return prepared + (table,), max_run, "dense"

    def _exec_right_join(self, node: JoinNode) -> PageStream:
        flipped = JoinNode(
            JoinKind.LEFT, node.right, node.left,
            tuple(JoinClause(c.right, c.left) for c in node.criteria),
            node.filter, node.distribution)
        stream = self.execute(flipped)
        return _reorder_stream(stream,
                               node.left.outputs + node.right.outputs)

    def _exec_full_join(self, node: JoinNode) -> PageStream:
        """FULL outer: LEFT-join streaming over probe pages while
        accumulating which build rows matched, then emit the never-matched
        build rows null-extended (LookupOuterOperator analog)."""
        from trino_tpu.ops.join import unmatched_build_page
        if node.filter is not None:
            raise ExecutionError(
                "non-inner join with residual filter not supported")
        probe_stream = self.execute(node.left)
        build_stream = self.execute(node.right)
        probe_lay, _ = _layout(probe_stream.symbols)
        build_lay, _ = _layout(build_stream.symbols)
        probe_keys = [probe_lay[c.left.name] for c in node.criteria]
        build_keys = [build_lay[c.right.name] for c in node.criteria]
        build_page = self._collect(build_stream)
        out_symbols = node.left.outputs + node.right.outputs
        probe_meta = tuple((s.type, None) for s in node.left.outputs)

        def full_op(cap: int):
            return cached_kernel(
                (_composite("join-full", probe_keys, outer=True),
                 tuple(probe_keys), tuple(build_keys), cap),
                lambda: hash_join(probe_keys, build_keys, JoinType.FULL,
                                  output_capacity=cap, prepared=True))

        def gen():
            import itertools
            nonlocal probe_meta
            bp = build_page
            if bp is None:
                bp = self._null_build_page(node.right.outputs)
            prepared = self._prepare_build(build_keys, bp, outer=True)
            matched = jnp.zeros(bp.capacity, dtype=jnp.bool_)
            it = self._coalesce_stream(probe_stream).iter_pages()
            while True:
                # lookahead-batched overflow resolution (same transfer
                # discipline as _run_with_overflow: one device_get per
                # window, not per page)
                batch = list(itertools.islice(it, 8))
                if not batch:
                    break
                results = []
                for page in batch:
                    probe_meta = tuple(
                        (c.type, c.dictionary) for c in page.columns)
                    cap = max(self.page_capacity, page.capacity)
                    results.append((cap, full_op(cap)(page, prepared)))
                totals = host_read([t for _, (_, t, _) in results],
                                   "probe_totals")
                for page, (cap, (out, _, bm)), total in zip(
                        batch, results, totals):
                    total = int(total)
                    if total > cap:
                        _count_overflow_rerun()
                    while total > cap:
                        cap = _next_pow2(total)
                        out, t, bm = full_op(cap)(page, prepared)
                        total = int(host_read(t, "probe_totals"))
                    matched = matched | bm
                    yield out
            if int(host_read(bp.num_rows, "build_rows")) == 0:
                return
            # once-per-query finisher: executed eagerly (its dictionaries
            # are per-query objects — caching on them would pin string
            # pools in the process-lifetime kernel cache forever)
            yield unmatched_build_page(probe_meta)(bp, matched)
        return PageStream(gen(), out_symbols)

    def _null_build_page(self, symbols: Tuple[Symbol, ...]) -> Page:
        cols = []
        for s in symbols:
            cols.append(Column(jnp.zeros(8, dtype=s.type.dtype),
                               jnp.zeros(8, dtype=jnp.bool_), s.type, None))
        return Page(tuple(cols), 0)

    def _exec_cross_join(self, node: JoinNode) -> PageStream:
        self._adaptive_event("cross_joins")
        probe_stream = self.execute(node.left)
        build_stream = self.execute(node.right)
        build_page = self._collect(build_stream)
        out_symbols = node.left.outputs + node.right.outputs

        def gen():
            if build_page is None:
                return
            nb = int(host_read(build_page.num_rows, "build_rows"))
            if nb == 1:
                # scalar-subquery path: broadcast the single build row
                def build():
                    def attach(p, b):
                        bcols = tuple(
                            Column(jnp.broadcast_to(c.values[:1],
                                                    (p.capacity,)),
                                   None if c.valid is None else
                                   jnp.broadcast_to(c.valid[:1],
                                                    (p.capacity,)),
                                   c.type, c.dictionary)
                            for c in b.columns)
                        return Page(tuple(p.columns) + bcols, p.num_rows)
                    return attach
                run = cached_kernel(("cross-attach",), build)
                for page in probe_stream.iter_pages():
                    yield run(page, build_page)
                return
            # general cross join: bounded expansion
            for page in probe_stream.iter_pages():
                np_rows = int(host_read(page.num_rows, "cross_probe_rows"))
                if np_rows == 0:
                    continue
                total = np_rows * nb
                if total > 4 * 1024 * 1024:
                    raise ExecutionError(
                        f"cross join too large ({total} rows)")
                cap = _next_pow2(total)
                idx = jnp.arange(cap, dtype=jnp.int32)
                pi = jnp.minimum(idx // nb, page.capacity - 1)
                bi = jnp.minimum(idx % nb, build_page.capacity - 1)
                pcols = tuple(c.gather(pi) for c in page.columns)
                bcols = tuple(c.gather(bi) for c in build_page.columns)
                yield Page(pcols + bcols, total)
        return PageStream(gen(), out_symbols)

    @staticmethod
    def _semijoin_filter_mode(node: FilterNode):
        """('semi'|'anti', rest_conjuncts) when the filter consumes the
        match flag as a plain top-level conjunct; None -> generic path."""
        semi: SemiJoinNode = node.source
        match_name = semi.match_symbol.name
        mode: Optional[str] = None
        rest: List[RowExpression] = []
        from trino_tpu.planner.optimizer import conjuncts
        for c in conjuncts(node.predicate):
            if isinstance(c, SymbolRef) and c.name == match_name:
                mode = "semi"
            elif isinstance(c, SpecialForm) and c.kind is SpecialKind.NOT \
                    and isinstance(c.args[0], SymbolRef) \
                    and c.args[0].name == match_name:
                mode = "anti"
            elif match_name in _symbol_names(c):
                return None
            else:
                rest.append(c)
        if mode is None:
            return None
        return mode, rest

    def _exec_semijoin_filter(self, node: FilterNode) -> PageStream:
        semi: SemiJoinNode = node.source
        from trino_tpu.planner.optimizer import combine
        mode, rest = self._semijoin_filter_mode(node)

        probe_stream = self.execute(semi.source)
        build_stream = self.execute(semi.filtering_source)
        probe_lay, probe_typ = _layout(probe_stream.symbols)
        build_lay, _ = _layout(build_stream.symbols)
        probe_keys = [probe_lay[s.name] for s in semi.source_keys]
        build_keys = [build_lay[s.name] for s in semi.filtering_keys]
        build_page = self._collect(build_stream)
        jt = JoinType.SEMI if mode == "semi" else JoinType.ANTI
        rest_pred = combine(rest)
        rest_lowered, rest_params = self._hoist(
            None if rest_pred is None else
            lower_expr(rest_pred, probe_lay, probe_typ))

        def semi_op(cap: int, mode: str = "search"):
            def build():
                op = _semi_probe(probe_keys, build_keys, jt, cap, mode,
                                 semi.null_aware)
                fn = None if rest_lowered is None \
                    else compile_filter(rest_lowered)

                def run(p, b, g):
                    out, total = op(p, b)
                    if fn is not None:
                        out = out.filter(fn(out, g))
                    # surviving rows all share one match value (semi: True,
                    # anti: False); emit it so pages carry EXACTLY the
                    # node's declared outputs — downstream operators lower
                    # expressions against declared layouts
                    mcol = Column(
                        jnp.broadcast_to(jnp.asarray(mode == "semi"),
                                         (out.capacity,)),
                        None, T.BOOLEAN, None)
                    return Page(out.columns + (mcol,), out.num_rows), total
                return run
            kernel = cached_kernel(
                ("semijoin", tuple(probe_keys), tuple(build_keys), jt,
                 cap, rest_lowered, semi.null_aware, mode), build,
                params=rest_params)
            return lambda p, b: kernel(p, b, rest_params)

        def gen():
            nonlocal build_page
            bp = build_page
            if bp is None:
                if jt == JoinType.SEMI:
                    return
                bp = self._null_build_page(semi.filtering_source.outputs)
            try:
                self._count_rows("semi_join_build_rows", bp.num_rows)
                prepared, _max_run, mode = self._prepare_probe(
                    build_keys, bp, semi=True)
                if mode == "set":
                    # the set table is all the probe reads: the build
                    # page's columns go before the first probe page comes
                    self._free_collected(build_page)
                    bp = build_page = None
                yield from _run_with_overflow(
                    self._lookup_lanes(self._counted(
                        self._coalesce_stream(probe_stream),
                        "semi_join_probe_rows")), prepared,
                    lambda cap: semi_op(cap, mode), self.page_capacity)
            finally:
                self._free_collected(build_page)
        return PageStream(gen(),
                          semi.source.outputs + (semi.match_symbol,))

    def _exec_SemiJoinNode(self, node: SemiJoinNode) -> PageStream:
        """Bare semi join: emit probe rows + boolean match channel
        (HashSemiJoinOperator). Used when the match symbol escapes a direct
        Filter (e.g. stacked EXISTS predicates)."""
        probe_stream = self.execute(node.source)
        build_stream = self.execute(node.filtering_source)
        probe_lay, _ = _layout(probe_stream.symbols)
        build_lay, _ = _layout(build_stream.symbols)
        probe_keys = [probe_lay[s.name] for s in node.source_keys]
        build_keys = [build_lay[s.name] for s in node.filtering_keys]
        build_page = self._collect(build_stream)
        out_symbols = node.source.outputs + (node.match_symbol,)

        def mark_op(cap: int, mode: str = "search"):
            return cached_kernel(
                ("markjoin", tuple(probe_keys), tuple(build_keys), cap,
                 node.null_aware, mode),
                lambda: _semi_probe(probe_keys, build_keys, JoinType.MARK,
                                    cap, mode, node.null_aware))

        def no_match(page: Page) -> Page:
            mark = Column(jnp.zeros(page.capacity, dtype=jnp.bool_), None,
                          T.BOOLEAN, None)
            return Page(tuple(page.columns) + (mark,), page.num_rows)

        def gen():
            nonlocal build_page
            bp = build_page
            if bp is None:
                for page in probe_stream.iter_pages():
                    yield no_match(page)
                return
            try:
                self._count_rows("semi_join_build_rows", bp.num_rows)
                prepared, _max_run, mode = self._prepare_probe(
                    build_keys, bp, semi=True)
                if mode == "set":
                    self._free_collected(build_page)
                    bp = build_page = None
                yield from _run_with_overflow(
                    self._lookup_lanes(self._counted(
                        self._coalesce_stream(probe_stream),
                        "semi_join_probe_rows")), prepared,
                    lambda cap: mark_op(cap, mode), self.page_capacity)
            finally:
                self._free_collected(build_page)
        return PageStream(gen(), out_symbols)

    def _exec_UnnestNode(self, node) -> PageStream:
        """UNNEST expansion (operator/unnest/UnnestOperator.java, static-
        shape cut): per page, element counts -> cumsum offsets -> one
        searchsorted maps output slots to source rows; elements gather
        from the [capacity, L] plane, replicated columns gather at the
        source row. Output capacity sizes from a per-page count fetch."""
        src = self.execute(node.source)
        lay, _ = _layout(src.symbols)
        arr_ch = lay[node.arrays[0].name]
        is_map = len(node.elements[0]) == 2
        with_ord = node.ordinality is not None

        def count_op_build():
            def run(page: Page):
                c = page.column(arr_ch)
                live = page.row_mask() & c.valid_mask()
                lens = jnp.where(live, c.lengths, 0)
                return jnp.sum(lens).astype(jnp.int64)
            return run
        count_op = cached_kernel(("unnest-count", arr_ch), count_op_build)

        def expand_op(cap: int):
            def build():
                def run(page: Page):
                    c = page.column(arr_ch)
                    n = page.capacity
                    L = c.values.shape[1]
                    live = page.row_mask() & c.valid_mask()
                    lens = jnp.where(live, c.lengths, 0).astype(jnp.int64)
                    offsets = jnp.cumsum(lens)
                    starts = offsets - lens
                    total = offsets[-1]
                    out_idx = jnp.arange(cap, dtype=jnp.int64)
                    prow = jnp.searchsorted(
                        offsets, out_idx, side="right").astype(jnp.int32)
                    prow_c = jnp.minimum(prow, n - 1)
                    within = (out_idx - jnp.take(starts, prow_c,
                                                 mode="clip")
                              ).astype(jnp.int32)
                    within_c = jnp.clip(within, 0, max(L - 1, 0))
                    cols = [col.gather(prow_c) for col in page.columns]
                    plane = jnp.take(c.values, prow_c, axis=0,
                                     mode="clip")
                    elem = jnp.take_along_axis(
                        plane, within_c[:, None], axis=1)[:, 0]
                    el_types = node.elements[0]
                    cols.append(Column(elem, None, el_types[0].type,
                                       c.dictionary))
                    if is_map:
                        aplane = jnp.take(c.aux, prow_c, axis=0,
                                          mode="clip")
                        aval = jnp.take_along_axis(
                            aplane, within_c[:, None], axis=1)[:, 0]
                        cols.append(Column(aval, None, el_types[1].type,
                                           c.aux_dictionary))
                    if with_ord:
                        cols.append(Column(within.astype(jnp.int64) + 1,
                                           None, T.BIGINT, None))
                    rows = jnp.minimum(total, cap).astype(jnp.int32)
                    return Page(tuple(cols), rows)
                return run
            return cached_kernel(
                ("unnest", arr_ch, cap, is_map, with_ord), build)

        def gen():
            for page in src.iter_pages():
                total = int(host_read(count_op(page), "unnest_total"))
                if total == 0:
                    continue
                yield expand_op(_next_pow2(total))(page)
        return PageStream(gen(), node.outputs)

    def _exec_AssignUniqueIdNode(self, node) -> PageStream:
        """AssignUniqueIdOperator: tag rows with a stable unique id.

        Ids are page_capacity_offset + row_position (NOT dense: padding rows
        consume ids too), so they are unique and — because scan order is
        deterministic — re-executing the same subtree (shared by a
        decorrelated EXISTS) reproduces identical ids."""
        src = self.execute(node.source)

        def build():
            def tag(page, offset):
                idx = (jnp.arange(page.capacity, dtype=jnp.int64)
                       + offset)
                col = Column(idx, None, T.BIGINT, None)
                return Page(tuple(page.columns) + (col,), page.num_rows)
            return tag
        tag = cached_kernel(("assign-unique-id",), build)

        def gen():
            # advance by page CAPACITY, not num_rows: padding rows get ids
            # too, so live rows of later pages can never collide with them
            # (uniqueness is this symbol's whole contract), and no per-page
            # num_rows host sync is needed
            offset = 0
            for page in src.iter_pages():
                yield tag(page, jnp.int64(offset))
                offset += page.capacity
        return PageStream(gen(), node.source.outputs + (node.id_symbol,))

    def _exec_EnforceSingleRowNode(self, node) -> PageStream:
        src = self.execute(node.source)

        def gen():
            page = self._collect(src)
            if page is None:
                # zero rows -> one all-null row (EnforceSingleRowOperator)
                yield Page(self._null_build_page(node.outputs).columns, 1)
                return
            n = int(host_read(page.num_rows, "single_row_rows"))
            if n > 1:
                raise ExecutionError(
                    "Scalar sub-query has returned multiple rows")
            yield page
        return PageStream(gen(), node.outputs)

    def _exec_UnionNode(self, node: UnionNode) -> PageStream:
        nsyms = len(node.symbols)

        def gen():
            # start every child and peek one page each: string columns from
            # different tables carry different dictionaries, and blocking
            # consumers (sort/agg/join build) concat across children — so
            # re-encode onto a shared union dictionary. Pages of one child
            # stream share a per-column dictionary, so one peek suffices.
            children = []
            for j, child in enumerate(node.children):
                stream = self.execute(child)
                lay, _ = _layout(stream.symbols)
                order = [lay[node.mappings[i][j].name] for i in range(nsyms)]
                it = iter(stream.iter_pages())
                first = next(it, None)
                children.append([it, first, order])
            remaps = _union_dictionary_remaps(node.symbols, children)
            for it, first, order in children:
                for page in _chain_first(first, it):
                    if int(host_read(page.num_rows, "union_rows")) == 0:
                        continue
                    cols = []
                    for i, ch in enumerate(order):
                        col = page.column(ch)
                        remap = remaps[i].get(id(col.dictionary)) \
                            if remaps[i] else None
                        if remap is not None:
                            table, union_dict = remap
                            codes = jnp.take(table,
                                             jnp.clip(col.values, 0),
                                             mode="clip")
                            col = Column(codes, col.valid, col.type,
                                         union_dict)
                        cols.append(col)
                    yield Page(tuple(cols), page.num_rows)
        return PageStream(gen(), node.symbols)

    def _exec_ExchangeNode(self, node: ExchangeNode) -> PageStream:
        # single-device execution: exchanges are pass-through (the
        # distributed executor lowers them to collectives)
        return self.execute(node.source)

    def _exec_WindowNode(self, node: WindowNode) -> PageStream:
        """WindowOperator: blocking sort-partitioned evaluation
        (operator/window/WindowOperator.java; ops/window.py kernel)."""
        from trino_tpu.ops.window import WindowSpec, window
        src = self.execute(node.source)
        lay, typ = _layout(src.symbols)
        part = tuple(lay[s.name] for s in node.partition_by)
        okeys = tuple(SortKey(lay[o.symbol.name], o.ascending, o.nulls_first)
                      for o in node.order_by)
        specs = []
        for out_sym, wf in node.functions:
            whole, bounds = self._lower_frame(node, wf)
            args = []
            for a in wf.args:
                if not isinstance(a, SymbolRef):
                    raise ExecutionError("window args must be pre-projected")
                args.append(lay[a.name])
            specs.append(WindowSpec(wf.name.lower(), tuple(args),
                                    out_sym.type, whole,
                                    wf.frame_type == "ROWS", bounds))
        win = cached_kernel(
            ("window", part, okeys, tuple(specs)),
            lambda: window(part, okeys, specs))

        def gen():
            page = self._collect(src)
            if page is None:
                return
            try:
                yield win(page)
            finally:
                self._free_collected(page)
        return PageStream(gen(), node.outputs)

    @staticmethod
    def _lower_frame(node: WindowNode, wf):
        """WindowFunction frame -> (frame_whole, bounds) for WindowSpec.

        Ranking functions ignore frames (SQL). The default/unbounded frames
        map onto the legacy whole/running paths; literal ROWS offsets become
        static (start_off, end_off) bounds; value-based RANGE offsets and
        GROUPS frames fail loud. Reference: FramedWindowFunction.java +
        sql/planner/plan/WindowNode.Frame."""
        from trino_tpu.ops.window import RANKING

        def literal_offset(value, kind: str) -> int:
            if not isinstance(value, Literal) or \
                    not isinstance(value.value, int):
                raise ExecutionError(
                    "window frame offsets must be integer literals")
            v = int(value.value)
            if v < 0:
                raise ExecutionError("window frame offset must be >= 0")
            return -v if kind == "PRECEDING" else v

        if wf.name.lower() in RANKING:
            return (not node.order_by), None
        st, sv = wf.start_type, wf.start_value
        et, ev = wf.end_type, wf.end_value
        if st == "UNBOUNDED_PRECEDING" and et == "UNBOUNDED_FOLLOWING":
            return True, None
        if not node.order_by:
            return True, None
        if st == "UNBOUNDED_PRECEDING" and et == "CURRENT_ROW":
            return False, None                     # running frame
        if wf.frame_type == "GROUPS":
            raise ExecutionError("GROUPS window frames not supported")
        if wf.frame_type == "RANGE":
            raise ExecutionError(
                "RANGE frames with value offsets not supported")
        start_off = None if st == "UNBOUNDED_PRECEDING" else (
            0 if st == "CURRENT_ROW" else literal_offset(sv, st))
        end_off = None if et == "UNBOUNDED_FOLLOWING" else (
            0 if et == "CURRENT_ROW" else literal_offset(ev, et))
        return False, (start_off, end_off)

    def _exec_OutputNode(self, node: OutputNode) -> PageStream:
        src = self.execute(node.source)
        return _reorder_stream(src, node.symbols)

    def _exec_TableWriterNode(self, node: TableWriterNode) -> PageStream:
        src = self.execute(node.source)
        lay, _ = _layout(src.symbols)
        order = [lay[s.name] for s in node.column_symbols]
        conn = self.metadata.connector(node.catalog)
        sink = conn.page_sink(node.table, write_token=self.write_token)
        if hasattr(sink, "set_commit_options"):
            # session manifest-log retention depth rides to the commit;
            # the MV refresher arms a replace-commit channel on the
            # session (internal, never SQL-settable): when THIS write's
            # target matches, the sink swaps the table's whole file set
            # and stamps the refresh watermark in the same commit
            opts = {"history": int(self.session.get(
                "lake_manifest_history"))}
            mv_commit = getattr(self.session, "_mv_commit", None)
            if mv_commit is not None and mv_commit.get("table") == (
                    node.catalog, node.table.name.schema,
                    node.table.name.table):
                opts["replace"] = bool(mv_commit.get("replace", True))
                opts["mv_meta"] = mv_commit.get("mv_meta")
            sink.set_commit_options(**opts)

        def gen():
            # idempotent-write protocol (connector/spi.py): pages STAGE
            # under the write token; finish() commits once per token.
            # Any failure — an injected fault, a slice-boundary cancel,
            # a killed victim, even generator abandonment — aborts the
            # staging, so a retried attempt starts from zero staged rows
            # and a committed token never commits twice.
            written = 0
            try:
                for page in src.iter_pages():
                    self._checkpoint()
                    n = int(host_read(page.num_rows, "writer_rows"))
                    if n == 0:
                        continue
                    out = Page(tuple(page.column(c) for c in order), n)
                    sink.append_page(out)
                    written += n
                sink.finish()
            except BaseException:   # GeneratorExit included: an
                sink.abort()        # abandoned writer must not leak
                raise               # staged rows into a later commit
            col = Column(jnp.asarray(np.array([written] * 8,
                                              dtype=np.int64)),
                         None, T.BIGINT, None)
            yield Page((col,), 1)
        return PageStream(gen(), node.outputs)


def _reorder_stream(src: PageStream, symbols: Tuple[Symbol, ...]
                    ) -> PageStream:
    """Select/reorder a stream's columns to `symbols` (identity is free)."""
    lay, _ = _layout(src.symbols)
    order = tuple(lay[s.name] for s in symbols)
    if order == tuple(range(len(src.symbols))):
        return PageStream(src.pages, symbols, src.pending, src.whole)
    return PageStream(
        src.pages, symbols,
        src.pending + ((("select", order),
                        lambda: lambda p, g: Page(
                            tuple(p.columns[c] for c in order),
                            p.num_rows), ()),), src.whole)




def _byte_bounded_batches(it: Iterator[Page], budget_bytes: int,
                          max_pages: int = 8) -> Iterator[List[Page]]:
    """Lookahead batching bounded by BYTES, not page count: dispatching 8
    32M-row probe buffers ahead of one sync pinned >10GB of intermediates
    in HBM at SF100 (the round-4 OOM). Small pages still amortize the sync
    across up to max_pages dispatches."""
    batch: List[Page] = []
    used = 0
    for page in it:
        nbytes = sum(c.nbytes for c in page.columns)
        if batch and (used + nbytes > budget_bytes
                      or len(batch) >= max_pages):
            yield batch
            batch, used = [], 0
        batch.append(page)
        used += nbytes
    if batch:
        yield batch


def _count_overflow_rerun() -> None:
    """One probe page whose join ran again at a larger output capacity:
    `probe_overflow_reruns` on the query's collector."""
    observer = get_observer()
    if hasattr(observer, "probe_overflow_reruns"):
        observer.probe_overflow_reruns += 1


def _semi_probe(probe_keys, build_keys, join_type: str, cap: int,
                mode: str, null_aware: bool):
    """The probe of a SEMI, ANTI or MARK join for the lookup
    `_prepare_probe(semi=True)` decided: one gather against the set
    table ('set'), else hash_join over the sorted build."""
    if mode == "set":
        return set_semi_join(probe_keys, join_type, null_aware)
    return hash_join(probe_keys, build_keys, join_type,
                     output_capacity=cap, prepared=True, lookup=mode,
                     null_aware=null_aware)


def _run_with_overflow(probe_stream: PageStream, build_page: Page,
                       make_op, page_capacity: int) -> Iterator[Page]:
    """Dispatch a capacity-laddered binary page op over probe pages in
    bounded lookahead windows, resolving each window's overflow counters in
    one batched device_get (a sync per page costs a full round trip on
    remote TPUs, but dispatching the whole stream before the first sync
    would pin every intermediate output in HBM simultaneously); only pages
    that actually overflowed re-run at the next capacity bucket (SURVEY §7
    contract), each counted once as `probe_overflow_reruns` from the total
    read anyway. Accepts a PageStream or a bare page iterator (the
    partitioned join streams restaged probe chunks directly)."""
    it = probe_stream.iter_pages() \
        if hasattr(probe_stream, "iter_pages") else iter(probe_stream)
    for probe_pages in _byte_bounded_batches(it, 1 << 29):
        results = []
        for page in probe_pages:
            cap = max(page_capacity, page.capacity)
            results.append((cap, make_op(cap)(page, build_page)))
        totals = host_read([t for _, (_, t) in results], "probe_totals")
        for page, (cap, (out, _)), total in zip(probe_pages, results,
                                                totals):
            total = int(total)
            if total > cap:
                _count_overflow_rerun()
            while total > cap:
                cap = _next_pow2(total)
                out, t = make_op(cap)(page, build_page)
                total = int(host_read(t, "probe_totals"))
            # join outputs inherit probe capacity; shrink heavily padded
            # ones so downstream sorts run at live size
            tight = _next_pow2(max(total, 1))
            if cap > 2 * tight:
                with observed_activity("eager_slice", "shrink_to"):
                    out = out.shrink_to(tight)
            yield out


def _chain_first(first: Optional[Page], rest: Iterator[Page]) -> Iterator[Page]:
    if first is not None:
        yield first
    yield from rest


def _drain_then(pages: List[Page], rest: Iterator[Page]) -> Iterator[Page]:
    """Yield the buffered pages DROPPING each reference as it is
    consumed (itertools.chain would pin the whole list — and its HBM —
    until exhaustion; this path exists precisely because memory is
    tight), then continue with the live stream."""
    while pages:
        yield pages.pop(0)
    yield from rest


def _union_dictionary_remaps(symbols, children):
    """Per output column: None when all children already share a dictionary,
    else {id(child_dict): (code_remap_device_array, union_dictionary)}."""
    from trino_tpu.page import union_dictionaries
    remaps: List[Optional[Dict[int, tuple]]] = []
    for i, sym in enumerate(symbols):
        dicts = []
        for it, first, order in children:
            if first is None:
                continue
            d = first.column(order[i]).dictionary
            if d is not None:
                dicts.append(d)
        uniq = {id(d): d for d in dicts}
        if len(uniq) <= 1:
            remaps.append(None)
            continue
        union, tables = union_dictionaries(list(uniq.values()))
        remaps.append({did: (tbl, union)
                       for did, tbl in zip(uniq, tables)})
    return remaps


def _valid_arr(valid: List[bool], cap: int) -> Optional[jnp.ndarray]:
    if all(valid):
        return None
    arr = np.zeros(cap, dtype=bool)
    arr[:len(valid)] = valid
    return jnp.asarray(arr)


def _symbol_names(e: RowExpression) -> set:
    out = set()

    def visit(x):
        if isinstance(x, SymbolRef):
            out.add(x.name)
        for c in x.children():
            visit(c)
    visit(e)
    return out
