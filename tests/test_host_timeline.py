"""The executor's host timeline (PR 39): what an executor thread does
inside `execution` is named where it happens — `obs/stats.activity`, two
clock reads into `host_ms`/`host_calls` and a profiler annotation
`host__<name>` — and compiles are counted where XLA reports them."""

import glob
import os
import random
import threading
import types

import jax
import jax.numpy as jnp
import pytest

from trino_tpu.exec import LocalQueryRunner, jit_cache, local_planner
from trino_tpu.obs import stats as obs_stats
from trino_tpu.obs.stats import (ACTIVITIES, QueryStatsCollector,
                                 maybe_activity)

import chip_smoke

QUERIES = {
    "q6": chip_smoke.Q6.format(date="1994-01-01", disc="0.06", qty=24),
    "q3": chip_smoke.Q3,
}


@pytest.fixture(scope="module")
def runner():
    r = LocalQueryRunner.tpch("tiny")
    r.session.set("result_cache_enabled", False)
    for sql in QUERIES.values():
        r.execute(sql)              # every program compiled once
    return r


@pytest.fixture
def dispatches(monkeypatch):
    """A counting stub of the jit cache: every callable the executor is
    handed by `cached_kernel` / `profiled_kernel` counts its calls."""
    seen = []

    def counting(lookup):
        def stub(*args, **kwargs):
            kernel = lookup(*args, **kwargs)

            def call(*a):
                seen.append(threading.get_ident())
                return kernel(*a)
            return call
        return stub
    for name in ("cached_kernel", "profiled_kernel"):
        stub = counting(getattr(jit_cache, name))
        monkeypatch.setattr(local_planner, name, stub)
        # the tpch connector looks `cached_kernel` up when it cuts pages
        # from a buffer another test's scan left at another capacity
        monkeypatch.setattr(jit_cache, name, stub)
    return seen


@pytest.fixture
def intervals(monkeypatch):
    """Every activity entered, as (thread, name, enter, exit) on one
    counter, and whether each was the innermost open one when it left."""
    recorded, clock = [], iter(range(10 ** 9))
    enter, leave = obs_stats._Activity.__enter__, obs_stats._Activity.__exit__
    open_now = {}           # thread -> [(activity, entered)], outermost first

    def on_enter(self):
        open_now.setdefault(threading.get_ident(), []).append(
            (self, next(clock)))
        return enter(self)

    def on_exit(self, *exc):
        out = leave(self, *exc)
        mine = open_now[threading.get_ident()]
        innermost = mine[-1][0] is self
        entered = next(t for a, t in mine if a is self)
        mine[:] = [(a, t) for a, t in mine if a is not self]
        recorded.append((threading.get_ident(), self._name, entered,
                         next(clock), innermost))
        return out
    monkeypatch.setattr(obs_stats._Activity, "__enter__", on_enter)
    monkeypatch.setattr(obs_stats._Activity, "__exit__", on_exit)
    return recorded


@pytest.mark.parametrize("shape", sorted(QUERIES))
def test_kernel_calls_are_the_dispatches_the_jit_cache_saw(
        runner, dispatches, shape):
    runner.execute(QUERIES[shape])
    stats = runner.last_query_stats
    assert stats["kernel_calls"] == len(dispatches) > 0
    assert stats["kernel_calls"] == stats["host_calls"]["kernel_call"]
    assert stats["host_reads"] == stats["host_calls"]["host_read"] > 0
    assert stats["page_pulls"] == stats["host_calls"]["page_pull"] > 0
    assert stats["backend_compiles"] == 0 and stats["jit_misses"] == 0


@pytest.mark.parametrize("shape", sorted(QUERIES))
def test_host_ms_is_self_time_inside_execution(runner, shape):
    runner.execute(QUERIES[shape])
    stats = runner.last_query_stats
    assert set(stats["host_ms"]) == set(stats["host_calls"]) \
        <= set(ACTIVITIES)
    assert {"kernel_call", "host_read", "page_pull", "to_host",
            "rows_to_python", "lower_plan"} <= set(stats["host_ms"])
    assert all(ms >= 0 for ms in stats["host_ms"].values())
    assert 0 < sum(stats["host_ms"].values()) <= 1e3 * stats["execution_s"]


@pytest.mark.parametrize("shape", sorted(QUERIES))
def test_no_activity_spans_a_yield(runner, intervals, shape):
    """On one thread any two recorded intervals nest or are disjoint, and
    each activity is the innermost open one when it leaves: a generator
    suspended inside one would break both."""
    runner.execute(QUERIES[shape])
    assert len(intervals) > 5
    assert all(innermost for *_, innermost in intervals)
    by_thread = {}
    for thread, _, a, b, _ in intervals:
        by_thread.setdefault(thread, []).append((a, b))
    for spans in by_thread.values():
        for a, b in spans:
            for c, d in spans:
                assert b < c or d < a or (a <= c and d <= b) \
                    or (c <= a and b <= d), ((a, b), (c, d))


def test_a_cached_kernel_traced_again_is_a_backend_compile():
    """ROADMAP measurement item 11's case: a kernel the cache holds,
    called with new avals, compiles inside `jax.jit`. `jit_misses` reads
    0 — it counts cache keys — and `backend_compiles` counts the compile,
    by name, with a `compile` span the first call did not need."""
    key = ("filter", "host-timeline-retrace")
    salt = random.random()      # never in the persistent compile cache
    build = lambda: lambda x: x * salt + 1          # noqa: E731
    jit_cache._CACHE.pop(key, None)
    first, again = QueryStatsCollector("q-first"), \
        QueryStatsCollector("q-again")
    short, long = jnp.arange(8.0), jnp.arange(24.0)     # eager: compiles
    try:
        jit_cache.set_observer(first)
        with first.phase("execution"):
            jit_cache.cached_kernel(key, build)(short)
        jit_cache.set_observer(again)
        with again.phase("execution"):
            jit_cache.cached_kernel(key, build)(long)
    finally:
        jit_cache.set_observer(None)
        jit_cache._CACHE.pop(key, None)
    cold, warm = first.snapshot(), again.snapshot()
    program = "jit(scan_filter__filter)"
    assert cold["jit_misses"] == 1 and program in cold["backend_compiled"]
    assert warm["jit_misses"] == 0 and warm["jit_compiles"] == 0
    assert warm["backend_compiles"] >= 1
    assert program in warm["backend_compiled"]
    assert warm["backend_compile_ms"] > 0 and warm["trace_lower_ms"] > 0
    # the first call lies under `_first_call`'s span; the retrace gets its
    # own, inside the kernel_call that held it, and leaves its self time
    assert len([s for s in cold["spans"] if s[0] == "compile"]) == 1
    assert len([s for s in warm["spans"] if s[0] == "compile"]) >= 1
    assert warm["host_calls"]["compile"] >= 1
    assert warm["host_ms"]["kernel_call"] + warm["host_ms"]["compile"] \
        <= 1e3 * warm["execution_s"]


def test_the_profiler_sees_the_activities_on_the_threads_own_line(
        runner, tmp_path):
    """Under a profiler session the `/host:` plane holds
    `request__execution` with `host__kernel_call` inside it on one line,
    on the trace's own clock."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1       # as benchmark/run.py's traced run
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        runner.execute(QUERIES["q6"])
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert found
    profile = jax.profiler.ProfileData.from_file(found[0])
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events
              if e.name.startswith(("host__", "request__"))]
             for plane in profile.planes if plane.name.startswith("/host:")
             for line in plane.lines]
    lines = [events for events in lines if events]
    assert len(lines) == 1              # the thread that ran the query
    names = {name for name, _, _ in lines[0]}
    assert {"request__execution", "request__result_fetch",
            "host__to_host", "host__rows_to_python"} <= names
    assert any(name.startswith("host__page_pull:") for name in names)
    (_, lo, hi), = [e for e in lines[0] if e[0] == "request__execution"]
    calls = [e for e in lines[0] if e[0].startswith("host__kernel_call:")]
    assert calls and all(lo <= a and b <= hi for _, a, b in calls)
    # (and the connector's page cut, where another test's scan left
    # lineitem's buffers at another capacity: no walk over those)
    assert {name.partition(":")[2] for name, _, _ in calls} \
        - {"scan_filter__page_cut"} == {
        "aggregate__chain_filter_project_agg_partial",
        "aggregate__agg_final"}
    reads = [e for e in lines[0] if e[0].startswith("host__host_read:")]
    assert reads and all(lo <= a and b <= hi for _, a, b in reads
                         if _ != "host__host_read:rows_on_device")


def test_without_a_collector_nothing_is_recorded_and_nothing_raises():
    with maybe_activity(None, "kernel_call", "anything"):
        pass
    jit_cache.set_observer(None)
    key = ("filter", "host-timeline-no-collector")
    jit_cache._CACHE.pop(key, None)
    try:
        fn = jit_cache.cached_kernel(key, lambda: lambda x: x + 1)
        assert int(fn(jnp.int32(1))) == 2
        assert int(jit_cache.host_read(fn(jnp.int32(2)), "nobody")) == 3
        assert list(jit_cache.pulled([1, 2], "connector")) == [1, 2]
        # with nobody to tell, the kernel handed out is the jitted
        # callable itself: no wrapper on the plain path
        assert jit_cache.cached_kernel(key, None) is jit_cache._CACHE[key][0]
    finally:
        jit_cache._CACHE.pop(key, None)


def test_no_annotation_is_made_without_a_profiler_session():
    """The always-on part is the two clock reads and the two sums; the
    annotation and its name exist only while a profiler would keep them."""
    col = QueryStatsCollector("q-no-session")
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with col.activity("kernel_call", "scan_filter__filter") as entry:
        assert entry._annotation is None
    assert col.host_n == {"kernel_call": 1}


def test_host_read_counts_device_values_and_lets_host_integers_pass():
    col = QueryStatsCollector("q-host-read")
    jit_cache.set_observer(col)
    try:
        assert jit_cache.host_read(7, "a_python_int") == 7
        assert col.host_n == {}
        got = jit_cache.host_read([jnp.int32(3), jnp.int32(4)], "a_site")
        assert [int(x) for x in got] == [3, 4]
    finally:
        jit_cache.set_observer(None)
    assert col.host_n == {"host_read": 1}
    # and with the collector handed over, on a thread that has none
    assert int(jit_cache.host_read(jnp.int32(5), "a_site", col)) == 5
    assert col.snapshot()["host_reads"] == 2


def test_an_activity_nested_in_another_is_taken_out_of_it(monkeypatch):
    """`host_s` is self time, so the values sum to no more than the wall
    they lie in — a compile XLA reports from inside a call included."""
    col = QueryStatsCollector("q-nested")
    ticks = iter((0, 1, 3, 4, 7, 8, 10))
    monkeypatch.setattr(obs_stats, "time", types.SimpleNamespace(
        monotonic=lambda: float(next(ticks))))
    with col.activity("page_concat"):                           # 0 .. 10
        with col.activity("host_read", "merge_counts"):         # 1 .. 3
            pass
        with col.activity("kernel_call", "scan_filter__dconcat"):   # 4 .. 8
            col.backend_compile("jit(scan_filter__dconcat)", 1.5,
                                reloaded=False, in_compile_span=False)
    assert col.host_n == {"host_read": 1, "kernel_call": 1, "compile": 1,
                          "page_concat": 1}
    assert col.host_s == {"host_read": 2.0, "kernel_call": 2.5,
                          "compile": 1.5, "page_concat": 4.0}
    assert sum(col.host_s.values()) == 10.0


def test_tight_is_an_eager_slice_of_the_threads_query():
    """`_tight` launches one eager slice a column where the page is more
    than twice its live rows' envelope; the thread's query is told."""
    import numpy as np
    from trino_tpu import types as T
    from trino_tpu.page import Page
    tight = local_planner.LocalExecutionPlanner._tight
    page = Page.from_numpy([np.arange(4096)], [T.BIGINT])
    col = QueryStatsCollector("q-tight")
    jit_cache.set_observer(col)
    try:
        assert tight(page, 1500) is page
        assert tight(page, 3).capacity == 1024
    finally:
        jit_cache.set_observer(None)
    # (the slice is an eager op: cold, XLA compiles it, a `compile` too)
    assert col.host_n["eager_slice"] == 2
    assert tight(page, 3).capacity == 1024          # and with nobody to tell


def test_explain_analyze_has_the_host_line(runner):
    text = runner.execute("EXPLAIN ANALYZE " + QUERIES["q6"]).rows[0][0]
    line = next(ln for ln in text.splitlines() if ln.startswith("host: "))
    assert " calls / " in line and " reads / " in line \
        and "kernel_call " in line and "backend compiles" in line


def test_runtime_queries_carries_the_counters(runner):
    runner.execute(QUERIES["q6"])
    rows = runner.execute(
        "SELECT kernel_calls, host_reads, backend_compiles "
        "FROM system.runtime.queries WHERE state = 'FINISHED' "
        "AND query LIKE '%l_extendedprice * l_discount%' "
        "AND query NOT LIKE '%runtime%'").rows
    assert rows and all(calls >= 2 and reads >= 1 and compiles >= 0
                        for calls, reads, compiles in rows[-1:])
