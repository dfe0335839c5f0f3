"""Module-scope compiled-pipeline cache.

Reference parity: sql/gen/PageFunctionCompiler.java:101 and
ExpressionCompiler.java:56 — the reference generates one PageProcessor class
per expression tree and caches it in a guava cache for the lifetime of the
server, so repeated queries never re-generate bytecode. Here the unit of
compilation is a jitted page kernel; the cache key is the lowered expression
tree / operator spec (frozen dataclasses, structurally hashable), and
jax.jit's own trace cache handles per-(capacity, dtype, dictionary) retraces
beneath each entry. Executing the same query shape twice must not re-trace.

Parameterized kernels (round 8): expr/hoist.py rewrites trace-shape-
irrelevant literals into Param slots before keys are built, so the key is
the literal-free CANONICAL tree and the literal values ride into the jitted
kernel as traced scalar operands (`params`). A hit whose parameter values
differ from the previous call of the same canonical key is a *param hit* —
sharing that per-literal keying could not have expressed (each distinct
literal set would have been its own key: a compile on first sight, a
separate resident kernel after). Counted separately so bench/metrics can
see the parameterized workload; note it counts value CHANGES against the
last call, not distinct literal sets, so alternating parameters re-count.

Compile-vs-execute accounting (round 13): `profiled_kernel` dispatches the
chain/program hot paths through per-input-signature AOT executables
(`fn.lower(*args).compile()`) managed HERE instead of inside jax.jit's
opaque dispatch cache. That makes every XLA compile an explicit, timed
event: the wall, the HLO instruction count, and the cost-model
flops/bytes record against the process counters AND the calling query's
collector (thread-local observer), so `compile_time_ms` in query stats is
measured, not inferred from cold-vs-warm deltas. A signature mismatch at
call time (defensive — shardings or weak types drifting) falls back to
the plain jitted callable rather than failing the query, counted as
`aot_fallbacks`.

The host's side of a dispatch (PR 39): every call of a program — the
dispatch `profiled_kernel` hands out and the callable `cached_kernel`
hands out — is the activity `kernel_call` of the calling query's
collector (`obs/stats.activity`; detail: the program's name), every
read of a device value by the executor thread goes through `host_read`
here, the activity `host_read` (detail: the site), and a scan pulls its
pages through `pulled`, the activity `page_pull`. Compiles are counted
where XLA reports them: one `jax.monitoring` listener, registered when
this module is imported, hears `backend_compile_duration` on the thread
that compiled and tells that thread's observer (`backend_compile`) — so a
cached kernel retraced for new avals, which compiles inside `jax.jit`
where neither `_first_call` nor `_aot_compile` sees it and which
`jit_misses` never counted, is a compile with a name. The event fires on
a persistent-compilation-cache hit too (there with the retrieval's
wall), after `/jax/compilation_cache/cache_hits` on the same thread: such
a one is counted as a reload, apart.

Interaction with the on-disk persistent XLA cache
(trino_tpu.enable_persistent_cache): this
LRU caches *loaded executables + traces in-process*; the persistent cache
stores *compiled XLA binaries on disk*, keyed by the traced program. An LRU
eviction (or a process restart) therefore costs a re-trace plus a disk
load, not a recompile — and because hoisted kernels are literal-free, one
disk entry serves every literal variant of a shape across processes.
"""

from __future__ import annotations

import collections
import contextlib
import numbers
import re
import threading
import time
from typing import Any, Callable, Dict, Hashable, Optional, Tuple

import jax
import jax.monitoring
import numpy as np

from trino_tpu.expr.hoist import LikeOperand
from trino_tpu.obs.stats import NO_ACTIVITY
from trino_tpu.page import device_notes, family_context, trace_notes

# key -> [jitted kernel, last-seen flattened param signature or None,
#         {input signature -> AOT compiled executable} (profiled path),
#         {input signature -> what the program's trace noted} (`named`),
#         whether the kernel was called yet (`cached_kernel`'s first call),
#         program_name(key)]
_CACHE: "collections.OrderedDict[Hashable, list]" = \
    collections.OrderedDict()
# concurrent queries (the server's executor pool) share this cache; the
# lock guards the LRU structure only — jitted kernels themselves are
# thread-safe to call
_LOCK = threading.RLock()   # reentrant: a build() may consult the cache
# LRU bound: every cached kernel pins a loaded XLA executable (JIT code
# pages + device buffers); unbounded growth across a long session exhausts
# executable memory maps. 512 is far above any single query's kernel count,
# so bench re-runs stay fully warm. Evicted kernels fall back to the
# on-disk persistent compilation cache (no re-trace cost beyond reload).
_MAX_KERNELS = 512

# process-lifetime hit/miss/param-hit/eviction counters (exported by
# obs/metrics.py) plus compile accounting: XLA compiles performed through
# the profiled path, their summed wall, summed HLO instruction counts,
# and cost-model flops/bytes — the process-level compile ledger behind
# every query's compile_time_ms. Plus a per-thread observer slot: the
# runner installs its query's QueryStatsCollector for the duration of
# execute(), so hits/misses/compiles attribute to the query whose
# executor thread triggered them.
_STATS = {"hits": 0, "misses": 0, "param_hits": 0, "evictions": 0,
          "compiles": 0, "compile_s": 0.0, "hlo_ops": 0,
          "aot_fallbacks": 0}
_TLS = threading.local()


def set_observer(observer) -> None:
    """Install/clear (None) this thread's per-query jit observer — an
    object with jit_hit(key)/jit_miss(key) and optionally
    jit_param_hit(key) / add_compile(wall_s, hlo_ops, nbytes) /
    activity(name, detail) / backend_compile(...)."""
    _TLS.observer = observer


def get_observer():
    """This thread's per-query observer (the executing query's
    QueryStatsCollector), or None outside runner.execute()."""
    return getattr(_TLS, "observer", None)


def observed_activity(name: str, detail: Optional[str] = None,
                      collector=None):
    """`collector.activity(name, detail)` (default: this thread's
    observer's, which on an executor thread is the query's collector), or
    a no-op where there is none to tell: the executor's one door to
    `obs/stats.activity`."""
    activity = getattr(collector if collector is not None
                       else get_observer(), "activity", None)
    return NO_ACTIVITY if activity is None else activity(name, detail)


def host_read(tree, site: str, collector=None):
    """`jax.device_get(tree)` as the activity `host_read` of `collector`
    (default: this thread's observer): the one door through which an
    executor thread reads a device value — a row count, a key range, an
    overflow flag — and so the one place where it stops to wait for the
    device. `site` names the call site (a bounded set). A Python or NumPy
    integer is on the host already: it passes through untimed and
    uncounted."""
    if isinstance(tree, numbers.Integral):
        return tree
    with observed_activity("host_read", site, collector):
        return jax.device_get(tree)


_NO_PAGE = object()


def pulled(pages, source: str):
    """`pages`, each pull of the next one the activity `page_pull` —
    `source` says where the page comes from: `connector`, `scan_cache` or
    `table_cache`. The activity closes before the page is handed on: it
    never spans a `yield`."""
    it = iter(pages)
    while True:
        with observed_activity("page_pull", source):
            page = next(it, _NO_PAGE)
        if page is _NO_PAGE:
            return
        yield page


# ---------------------------------------------------------------------------
# compiles, as XLA reports them (the module docstring)

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE_LOWER = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _on_duration_event(event: str, duration_s: float, **kwargs) -> None:
    if event == _BACKEND_COMPILE:
        reloaded = getattr(_TLS, "cache_hit", False)
        _TLS.cache_hit = False
        report = getattr(get_observer(), "backend_compile", None)
        if report is not None:
            report(str(kwargs.get("fun_name", "?")), duration_s, reloaded,
                   getattr(_TLS, "in_compile_span", False))
    elif event in _TRACE_LOWER:
        observer = get_observer()
        if hasattr(observer, "trace_lower_s"):
            observer.trace_lower_s += duration_s


def _on_event(event: str, **kwargs) -> None:
    if event == _CACHE_HIT:     # the next backend_compile here is a reload
        _TLS.cache_hit = True


jax.monitoring.register_event_duration_secs_listener(_on_duration_event)
jax.monitoring.register_event_listener(_on_event)


@contextlib.contextmanager
def _compiling(name: str):
    """The activity `compile` around one of the jit cache's own compile
    sites, which stamp their own `compile` span: a compile XLA reports
    from inside is not given a second one."""
    _TLS.in_compile_span = True
    try:
        with observed_activity("compile", name):
            yield
    finally:
        _TLS.in_compile_span = False


def _param_signature(params) -> Tuple:
    """Flatten a (possibly nested) tuple of scalar/vector arrays into a
    comparable value signature. Used only to tell `jit_param_hit` (same
    canonical key, new literal values) apart from a plain `jit_hit`.
    Vector entries (padded IN-list members) compare by shape + raw
    bytes, so a reordered or repadded member list counts as a value
    change just like a perturbed scalar."""
    out = []

    def visit(p):
        if isinstance(p, (tuple, list)):
            for x in p:
                visit(x)
        elif isinstance(p, LikeOperand):
            # a LIKE table not built yet: its pattern is its value
            out.append(("like", p.pattern, p.escape))
        else:
            a = np.asarray(p)
            out.append((a.dtype.str, a.shape, a.tobytes()))
    visit(params)
    return tuple(out)


# Program names (round 25): every jitted program is named from its cache
# key, `<family>__<tag>[_<tag>...]` in [a-z0-9_], so the device trace's
# `XLA Modules` line and each op's scope path say which operator a device
# second belongs to. Tags only — never expression text or literals — so
# the set of names is bounded by the set of kernel kinds. A tag matches a
# family by its longest listed prefix; an unlisted tag is `misc`
# (tests/test_program_names.py lists those).
NAME_GRAMMAR = re.compile(
    r"^(scan_filter|aggregate|join|sort|window|exchange|misc)"
    r"__[a-z0-9]+(_[a-z0-9]+)*$")
_FAMILY_PREFIXES = (
    ("scan_filter", ("filter", "project", "select", "dconcat", "unnest",
                     "assign-unique-id", "tpch-generate", "page-cut")),
    ("aggregate", ("agg",)),
    ("join", ("join", "uprobe", "uattach", "semijoin", "markjoin",
              "cross-attach", "dense-table", "dfbounds",
              "dfrange", "probe-compact", "spill-prep", "spill-probe")),
    ("sort", ("sort", "topn", "merge-sort")),
    ("window", ("window",)),
    ("exchange", ("exchange", "mesh-prog", "mesh-sconcat")),
)


def key_tag(part) -> Optional[str]:
    """The leading tag of a key or of one chain step's key."""
    while isinstance(part, tuple) and part:
        part = part[0]
    return part if isinstance(part, str) else None


def family_of(tag: str) -> str:
    best, family = -1, "misc"
    for name, prefixes in _FAMILY_PREFIXES:
        for p in prefixes:
            if len(p) > best and (tag == p or tag.startswith(p + "-")):
                best, family = len(p), name
    return family


def program_name(key: Hashable) -> str:
    """`<family>__<tag>[_<tag>...]` for a cache key. A chain is named by
    its steps' tags and takes the family of its blocking tail (the last
    step that is not scan/filter work), else `scan_filter`."""
    head = key_tag(key) or "untagged"
    if head == "chain":
        steps = [key_tag(k) or "untagged" for k in key[1:]]
        blocking = [f for f in map(family_of, steps) if f != "scan_filter"]
        family = blocking[-1] if blocking else "scan_filter"
        tags = [head] + steps
    else:
        family, tags = family_of(head), [head]
    clean = (re.sub(r"[^a-z0-9]+", "_", t.lower()).strip("_") or "x"
             for t in tags)
    return f"{family}__{'_'.join(clean)}"


def _aval_signature(args) -> Tuple:
    """treedef + per-leaf (dtype, shape): what a trace depends on, the
    same for the tracers a program is traced with and for the arrays it
    is dispatched with (`profiler.tree_signature` adds the sharding, which
    a trace does not see)."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (treedef,) + tuple(
        (jax.numpy.result_type(leaf).str, np.shape(leaf))
        for leaf in leaves)


@jax.tree_util.register_pytree_node_class
class _Said:
    """What a program returns where its trace said numbers through
    `page.note_device`: its own output and those scalars, by name."""

    def __init__(self, out, names, values):
        self.out, self.names, self.values = out, names, values

    def tree_flatten(self):
        return (self.out, self.values), self.names

    @classmethod
    def tree_unflatten(cls, names, children):
        return cls(children[0], names, children[1])


def _heard(out):
    """A dispatch's output as its caller expects it: what the program
    said beside it (`_Said`) goes to the thread's observer as a row count
    does (obs/stats.count_rows: still on the device, read at the query's
    end), or to nobody."""
    if type(out) is not _Said:
        return out
    count = getattr(get_observer(), "count_rows", None)
    if count is not None:
        for name, value in zip(out.names, out.values):
            count(name, value)
    return out.out


class _Program:
    """A jitted program as the cache keeps it and hands it out: a call
    takes what the program said beside its output off again (`_heard`);
    `lower` is the jitted callable's, for the AOT path."""

    __slots__ = ("jitted", "lower", "__name__")

    def __init__(self, jitted):
        self.jitted, self.lower = jitted, jitted.lower
        self.__name__ = jitted.__name__

    def __call__(self, *args):
        return _heard(self.jitted(*args))


def named(fn: Callable, key: Hashable) -> Callable:
    """`fn` under `program_name(key)`: jax.jit names the module, and the
    root of every op's scope path, after the function it is given. A
    wrapper rather than a rename, so a builder may return a shared
    function. Traced once per signature; not on the dispatch path.

    `program.noted` keeps, per input signature, what the traced code said
    through `page.note_trace`: static facts of the program that
    `profiled_kernel` counts on the query's collector at each dispatch.
    What it said through `page.note_device` leaves the program beside its
    output (`_Said`); `_heard` takes it off again at every dispatch — a
    `_Program`'s call, or the AOT executable's in `profiled_kernel`."""
    name = program_name(key)
    family = name.partition("__")[0]
    noted: Dict[Tuple, frozenset] = {}

    def program(*args):
        with family_context(family), trace_notes() as notes, \
                device_notes() as said:
            out = fn(*args)
        if notes:
            noted[_aval_signature(args)] = frozenset(notes)
        if said:
            return _Said(out, tuple(name for name, _ in said),
                         tuple(value for _, value in said))
        return out
    program.__name__ = program.__qualname__ = name
    program.noted = noted
    return program


def _lookup(key: Hashable, build: Callable[[], Callable],
            params: Optional[Any]) -> list:
    """Shared LRU lookup: returns the entry list, counting hit/miss and
    param-hit exactly as before, and notifying the thread observer."""
    sig = None if params is None else _param_signature(params)
    param_hit = False
    with _LOCK:
        entry = _CACHE.get(key)
        if entry is None:
            program = named(build(), key)
            fn = _Program(jax.jit(program))
            while len(_CACHE) >= _MAX_KERNELS:
                _CACHE.popitem(last=False)
                _STATS["evictions"] += 1
            entry = _CACHE[key] = [fn, sig, {}, program.noted, False,
                                   program.__name__]
            _STATS["misses"] += 1
            miss = True
        else:
            _CACHE.move_to_end(key)
            _STATS["hits"] += 1
            miss = False
            if sig is not None:
                param_hit = entry[1] is not None and entry[1] != sig
                entry[1] = sig
                if param_hit:
                    _STATS["param_hits"] += 1
    observer = get_observer()
    if observer is not None:
        (observer.jit_miss if miss else observer.jit_hit)(key)
        if param_hit and hasattr(observer, "jit_param_hit"):
            observer.jit_param_hit(key)
    return entry


def cached_kernel(key: Hashable, build: Callable[[], Callable],
                  params: Optional[Any] = None) -> Callable:
    """Return the jitted kernel for `key`, building+jitting it on first use.

    `build()` must construct the kernel purely from information encoded in
    `key` (no capture of per-query state), so a cache hit is always correct.
    `params`, when given, is the runtime literal tuple the caller will pass
    to the kernel — used ONLY for hit attribution (param-hit vs plain hit),
    never for keying: the whole point is that the key excludes it.
    """
    entry = _lookup(key, build, params)
    fn = entry[0] if entry[4] else _first_call(entry)
    fenced, activity = _fencing_observer(), _observers_activity()
    if activity is None and fenced is None:
        return fn                   # nobody to tell: the cached callable
    activity, name, noted = activity or _no_activity, entry[5], entry[3]

    def dispatch(*args):
        with activity("kernel_call", name):
            out = fn(*args) if fenced is None else _timed(fn, args, fenced)
        if noted:
            # a program whose trace said something (page.note_trace; the
            # first call has traced by now): few do, and only they pay
            # the signature
            _count_notes(noted.get(_aval_signature(args)), chain=False)
        return out
    return dispatch


def _first_call(entry: list) -> Callable:
    """A kernel nobody called yet: its first call traces and compiles
    inside `jax.jit`, so that call lies under a `compile` span of the
    calling query (obs/stats.compile_span) — the device's idle time in it
    is a cold program's, not dispatch. Every later call, and every later
    lookup, is the jitted callable's own."""
    fn = entry[0]

    def call(*args):
        if entry[4]:
            return fn(*args)
        entry[4] = True
        span = getattr(get_observer(), "compile_span", None)
        if span is None:
            return fn(*args)
        t0 = time.monotonic()
        try:
            with _compiling(entry[5]):
                return fn(*args)
        finally:
            span(t0, time.monotonic())
    return call


def _count_notes(notes, chain: bool = True) -> None:
    """One dispatch of a program whose trace noted `notes`, told to the
    thread's observer (obs/stats.count_program_notes)."""
    if notes:
        count = getattr(get_observer(), "count_program_notes", None)
        if count is not None:
            count(notes, chain)


def _fencing_observer():
    """This thread's observer if its query measures device time (operator-
    level collection or EXPLAIN ANALYZE), else None. Asked when a kernel
    is looked up — once per operator, not per dispatch."""
    observer = get_observer()
    return observer if getattr(observer, "fenced", False) else None


def _observers_activity():
    """This thread's observer's `activity`, or None. Asked when a kernel
    is looked up, as whether to fence is: a dispatch then reads no
    thread-local and tests no attribute."""
    return getattr(get_observer(), "activity", None)


def _no_activity(name, detail=None):
    return NO_ACTIVITY


def _timed(fn, args: tuple, observer):
    """One fenced dispatch: pin the asynchronously dispatched work with
    block_until_ready and add its wall to the query's device time. Every
    kernel of a fenced query comes through here — chains, joins,
    aggregates, sorts, mesh programs — so what execution has left after
    device and compile time is the host's. (A `cached_kernel` program's
    first call compiles inside `fn`: cold, that wall lands here.)"""
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    observer.add_device_time(time.perf_counter() - t0)
    return out


def _aot_compile(name: str, fn, args: tuple, arg_sig, aot: dict):
    """Lower + compile one executable for this input signature, timed:
    the explicit XLA-compile event behind compile_time_ms. Records the
    wall and the HLO instruction count on the process ledger and, with
    the cost model's bytes, on the calling query's collector. Concurrent
    losers of the publish race discard their duplicate and record
    NOTHING — the ledger counts real resident executables, not wasted
    work (full in-flight dedup would need a per-signature latch; the
    duplicated compile is rare and harmless, the double-count would
    not be)."""
    from trino_tpu.obs import profiler
    t0 = time.monotonic()       # the spans' clock: this wall is a span
    with _compiling(name):
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    t1 = time.monotonic()
    wall = t1 - t0
    ops = profiler.hlo_op_count(lowered)
    cost = profiler.cost_dict(lowered)
    with _LOCK:
        existing = aot.get(arg_sig)
        if existing is not None:
            return existing     # lost the race: one executable, one event
        aot[arg_sig] = compiled
        _STATS["compiles"] += 1
        _STATS["compile_s"] += wall
        _STATS["hlo_ops"] += ops
    observer = get_observer()
    if observer is not None and hasattr(observer, "add_compile"):
        observer.add_compile(wall, hlo_ops=ops,
                             nbytes=cost.get("bytes", 0.0), end_s=t1)
    return compiled


def profiled_kernel(key: Hashable, build: Callable[[], Callable],
                    params: Optional[Any] = None) -> Callable:
    """cached_kernel with compile-vs-execute accounting: dispatch runs
    through per-input-signature AOT executables owned by the cache entry,
    so every XLA compile is a timed, attributed event instead of a stall
    hidden inside jax.jit's first call. Same key space, same hit/miss/
    param-hit counters as cached_kernel — a key warmed by one path is
    warm for the other."""
    fn, _, aot, noted, _, name = _lookup(key, build, params)
    fenced = _fencing_observer()
    activity = _observers_activity() or _no_activity
    from trino_tpu.obs import profiler

    def _fallback(*args):
        # never fail (or silently slow) a query over accounting: the
        # plain jitted callable always works; the counter makes a
        # systematic fallback visible in /v1/metrics
        with _LOCK:
            _STATS["aot_fallbacks"] += 1
        return fn(*args)

    def dispatch(*args):
        with activity("kernel_call", name):
            return _heard(enqueue(*args))

    def enqueue(*args):
        # per-dispatch signature cost is ~10us of pytree flattening —
        # small against the >=100us python dispatch + kernel launch a
        # page already pays, and it is what detects the retrace
        # (new-signature) compiles the accounting exists to expose
        try:
            arg_sig = profiler.tree_signature(args)
            compiled = aot.get(arg_sig)
            if compiled is None:
                compiled = _aot_compile(name, fn, args, arg_sig, aot)
        except Exception:
            return _fallback(*args)
        notes = noted.get(arg_sig)
        if notes is None:
            # first dispatch of this signature: look its trace's notes up
            # by what a trace sees, and keep them under the signature the
            # dispatch already has in hand
            notes = noted[arg_sig] = noted.get(_aval_signature(args),
                                               frozenset())
        _count_notes(notes)
        try:
            if fenced is None:
                return compiled(*args)
            return _timed(compiled, args, fenced)   # compile wall is out
        except (TypeError, ValueError):
            # aval/sharding mismatch at CALL time (signature drift the
            # tree signature failed to capture) — re-dispatch through
            # the jitted callable. Real kernel failures (device OOM,
            # runtime errors) are neither TypeError nor ValueError and
            # PROPAGATE: swallowing them would silently re-execute the
            # whole program at the worst possible moment.
            return _fallback(*args)
    return dispatch


def cache_info() -> int:
    return len(_CACHE)


def stats() -> dict:
    """Snapshot for metrics: resident kernels + lifetime hits/misses/
    param-hits (hit on a canonical key with changed literal values) /
    evictions, and the compile ledger (profiled-path XLA compiles, their
    summed wall and HLO instruction counts, AOT dispatch fallbacks)."""
    with _LOCK:
        return {"size": len(_CACHE), "hits": _STATS["hits"],
                "misses": _STATS["misses"],
                "param_hits": _STATS["param_hits"],
                "evictions": _STATS["evictions"],
                "compiles": _STATS["compiles"],
                "compile_s": _STATS["compile_s"],
                "hlo_ops": _STATS["hlo_ops"],
                "aot_fallbacks": _STATS["aot_fallbacks"]}


def clear():  # for tests
    with _LOCK:
        _CACHE.clear()
