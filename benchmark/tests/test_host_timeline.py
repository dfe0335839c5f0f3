"""host_timeline.py on hand-made planes: the traced slice by executor
thread and activity, in the trace's own nanoseconds."""

import importlib
import json
import os

import pytest

import host_timeline
import trace_programs
import trace_reduce

MS = 1_000_000          # ns
T_BEGIN = 100.0         # time.monotonic() at bench_slice_begin
LO = 5 * MS             # ... and where that annotation lies in the trace
NEW_METRICS = ("idle_host_unnamed_share", "kernel_calls_per_q",
               "host_reads_per_q", "kernel_call_host_us",
               "backend_compiles_in_window")


def _events(*events):
    """(name, start_ms, end_ms) relative to the slice's begin."""
    return [({"name": name, "display_name": "", "stats": {}},
             LO + int(a * MS), int((b - a) * MS)) for name, a, b in events]


def _planes(window_ms, device_ops, *threads):
    marks = _events((trace_reduce.BEGIN, 0, 0.001),
                    (trace_reduce.END, window_ms, window_ms + 0.001))
    return [
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": marks}]
            + [{"name": "python", "events": _events(*t)} for t in threads]},
        {"name": "/device:TPU:0", "lines": [
            {"name": trace_reduce.OPS_LINE, "events": _events(*device_ops)}]},
    ]


def _mono(ms):
    return T_BEGIN + ms * 1e-3


@pytest.fixture
def planes(monkeypatch):
    """`set(planes)`: what the decoder reads from any path."""
    box = {}
    monkeypatch.setattr(trace_programs, "read_xspace",
                        lambda path, **kw: box["planes"])
    return lambda p: box.update(planes=p)


def test_one_thread(planes):
    """Every second of the slice, idle and busy, by what the one executor
    thread was inside."""
    planes(_planes(
        1000, [("%fusion = fusion()", 100, 200)],
        [("request__execution", 50, 900),
         ("host__kernel_call:aggregate__agg_final", 60, 110),
         ("host__host_read:merge_counts", 110, 300)]))
    t = host_timeline.reduce("x", [[["execution", _mono(50), _mono(900)]]],
                             T_BEGIN)
    assert t["window_s"] == pytest.approx(1.0)
    assert t["idle_s"] == pytest.approx(0.9)
    assert t["idle_by_activity"] == pytest.approx({
        "no_request": 0.150, "interpreter": 0.610, "kernel_call": 0.040,
        "host_read": 0.100})
    assert t["busy_by_activity"] == pytest.approx({
        "kernel_call": 0.010, "host_read": 0.090})
    assert t["threads"] == 1
    assert t["clock_skew_ms"] == pytest.approx(0.0, abs=1e-6)


def test_idle_is_where_none_of_the_cells_chips_ran(planes):
    """A mesh cell: a gap is where no chip of the cell ran, a chip that
    is not the cell's is not read, and the parts still sum to the idle
    seconds."""
    mesh = _planes(
        1000, [("%fusion = fusion()", 100, 400)],
        [("request__execution", 0, 1000),
         ("host__host_read:mesh_program_aux", 200, 700)])
    for chip, ops in ((1, [("%all-to-all = all-to-all()", 300, 600)]),
                      (2, [("%fusion = fusion()", 0, 1000)])):
        mesh.append({"name": f"/device:TPU:{chip}", "lines": [
            {"name": trace_reduce.OPS_LINE, "events": _events(*ops)}]})
    planes(mesh)
    t = host_timeline.reduce("x", [[["execution", _mono(0), _mono(1000)]]],
                             T_BEGIN, chips=(0, 1))
    assert t["idle_s"] == pytest.approx(0.5)
    assert t["idle_by_activity"] == pytest.approx({
        "interpreter": 0.4, "host_read": 0.1})
    assert t["busy_by_activity"] == pytest.approx({"host_read": 0.4,
                                                   "interpreter": 0.1})
    assert sum(t["idle_by_activity"].values()) == pytest.approx(t["idle_s"])


def test_a_gap_is_shared_by_thread_not_given_by_priority(planes):
    """Three threads executing through a gap, one of them in `to_host`:
    a third of it is `to_host`'s and two thirds the interpreter's, where
    `trace_programs.idle_by_span` gives all of it to `result_fetch`."""
    planes(_planes(
        100, [("%fusion = fusion()", 0, 40), ("%fusion = fusion()", 70, 100)],
        [("request__execution", 0, 100), ("request__result_fetch", 30, 90),
         ("host__to_host", 35, 80)],
        [("request__execution", 0, 100)],
        [("request__execution", 0, 100),
         ("host__kernel_call:join__uprobe", 0, 40)]))
    spans = [[["execution", _mono(0), _mono(100)],
              ["result_fetch", _mono(30), _mono(90)]],
             [["execution", _mono(0), _mono(100)]],
             [["execution", _mono(0), _mono(100)]]]
    t = host_timeline.reduce("x", spans, T_BEGIN)
    assert t["idle_s"] == pytest.approx(0.030)
    assert t["idle_by_activity"] == pytest.approx({
        "to_host": 0.010, "interpreter": 0.020})
    assert sum(t["idle_by_activity"].values()) == pytest.approx(t["idle_s"])
    assert sum(t["busy_by_activity"].values()) == pytest.approx(0.070)
    assert t["busy_by_activity"]["kernel_call"] == pytest.approx(0.040 / 3)
    assert t["threads"] == 3
    assert t["busy_by_activity"]["to_host"] == pytest.approx(0.015 / 3)
    old = trace_programs.reduce("x", spans, T_BEGIN)
    assert old["idle_by_span"] == pytest.approx({"result_fetch": 0.030})


def test_nested_activities_the_innermost_wins(planes):
    planes(_planes(
        100, [],
        [("request__execution", 0, 100), ("host__page_concat", 10, 60),
         ("host__host_read:merge_counts", 10, 30),
         ("host__kernel_call:scan_filter__dconcat", 30, 40)]))
    t = host_timeline.reduce("x", [], T_BEGIN)
    assert t["idle_by_activity"] == pytest.approx({
        "interpreter": 0.050, "host_read": 0.020, "kernel_call": 0.010,
        "page_concat": 0.020})


def test_nobody_executing_is_planning_queued_or_no_request(planes):
    planes(_planes(
        100, [],
        [("request__planning", 10, 20), ("request__execution", 20, 50),
         ("host__page_pull:table_cache", 21, 22)]))
    spans = [[["queued", _mono(5), _mono(10)],
              ["planning", _mono(10), _mono(20)],
              ["execution", _mono(20), _mono(50)]],
             [["queued", _mono(40), _mono(70)]]]
    t = host_timeline.reduce("x", spans, T_BEGIN)
    assert t["idle_by_activity"] == pytest.approx({
        "no_request": 0.005 + 0.030, "queued_only": 0.005 + 0.020,
        "planning": 0.010, "interpreter": 0.029, "page_pull": 0.001})


def test_a_query_cut_by_the_session_keeps_its_thread(planes):
    """The profiler drops an annotation that began before the session or
    ends after it: a query under way at either edge has activities on its
    line and no `request__execution`. `stats.spans` says it was
    executing; the thread that holds its activities is the one."""
    planes(_planes(
        100, [("%fusion = fusion()", 50, 100)],
        [("host__host_read:probe_totals", 5, 30),           # cut at start
         ("request__execution", 40, 60),
         ("host__kernel_call:join__uprobe", 70, 95)],       # cut at end
        [("request__execution", 0.5, 99),
         ("host__to_host", 10, 20)]))
    spans = [[["execution", _mono(-500), _mono(35)]],
             [["execution", _mono(40), _mono(60)]],
             [["execution", _mono(65), _mono(400)]],
             [["execution", _mono(0.5), _mono(99)]]]
    t = host_timeline.reduce("x", spans, T_BEGIN)
    assert t["threads"] == 2
    # idle [0, 50): A executes [0, 35) and [40, 50), B [0.5, 50)
    assert t["idle_by_activity"] == pytest.approx({
        "host_read": 0.0125, "to_host": 0.005, "interpreter": 0.0325})
    assert sum(t["idle_by_activity"].values()) == pytest.approx(0.050)
    assert "no_request" not in t["idle_by_activity"]
    # busy [50, 100): A's kernel_call counts though its request was cut
    assert t["busy_by_activity"]["kernel_call"] == pytest.approx(0.0125)
    # without the spans the cut query's thread reads as not executing
    bare = host_timeline.reduce("x", [], T_BEGIN)
    assert bare["idle_by_activity"]["no_request"] == pytest.approx(0.0005)
    assert bare["busy_by_activity"].get("kernel_call", 0.0) == 0.0


def test_clock_skew_is_the_spans_against_the_annotations(planes):
    planes(_planes(
        100, [],
        [("request__execution", 10, 20), ("host__to_host", 12, 13),
         ("request__execution", 30, 40), ("request__execution", 50, 60)]))
    spans = [[["execution", _mono(10.4), _mono(20)]],
             [["execution", _mono(29.8), _mono(40)]],
             [["execution", _mono(50.3), _mono(60)]]]
    t = host_timeline.reduce("x", spans, T_BEGIN)
    assert t["clock_skew_ms"] == pytest.approx(0.3, abs=1e-6)


def test_a_program_without_the_activities_reads_none(planes, monkeypatch):
    """The parent's trace: request spans, no `host__*` event, no counter
    in the stats — every new metric is left out, none raises."""
    planes(_planes(
        100, [("%fusion = fusion()", 0, 40)],
        []))
    assert host_timeline.reduce("x", [], T_BEGIN) is None
    monkeypatch.setattr(trace_programs, "newest_xplane", lambda root: "x")
    stats = {"result_cache_hits": 0, "jit_misses": 0,
             "spans": [["execution", _mono(0), _mono(100)]]}
    ctx = {"requests": [{"info": {"stats": stats}, "t_send": _mono(0),
                         "t_done": _mono(100)}],
           "trace": {"busy_s": 0.04}, "slice": (T_BEGIN, _mono(100)),
           "chips": [0]}
    for name in NEW_METRICS:
        metric = importlib.import_module(f"layer_metrics.{name}")
        assert metric.read(ctx) is None, name


def test_the_table_of_a_run_is_reduced_once_and_kept_whole(
        planes, monkeypatch):
    """`table(ctx)`: the reduced slice of this process's xplane, cached on
    `ctx` and written to .bench_out/host_timeline.json — in every cell:
    `kernel_calls_per_q`, which all six list, asks for it."""
    planes(_planes(
        100, [("%fusion = fusion()", 0, 40)],
        [("request__execution", 0, 100),
         ("host__kernel_call:join__join_prep", 10, 90)]))
    monkeypatch.setattr(trace_programs, "newest_xplane", lambda root: "x")
    out = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".bench_out")
    os.makedirs(out, exist_ok=True)
    kept = os.path.join(out, "host_timeline.json")
    if os.path.exists(kept):
        os.remove(kept)
    stats = [{"result_cache_hits": 0, "kernel_calls": 3,
              "spans": [["execution", _mono(0), _mono(100)]]},
             {"result_cache_hits": 0, "kernel_calls": 5, "spans": []}]
    ctx = {"requests": [{"info": {"stats": s}} for s in stats],
           "trace": {"busy_s": 0.04}, "slice": (T_BEGIN, _mono(100)),
           "chips": [0]}
    calls = importlib.import_module("layer_metrics.kernel_calls_per_q")
    assert calls.read(ctx) == pytest.approx(4.0)
    with open(kept) as f:
        assert json.load(f)["idle_by_activity"] == pytest.approx({
            "interpreter": 0.010, "kernel_call": 0.050})
    t = host_timeline.table(ctx)
    assert t is host_timeline.table(ctx)        # cached on ctx
    assert t["busy_by_activity"] == pytest.approx({
        "interpreter": 0.010, "kernel_call": 0.030})
    unnamed = importlib.import_module(
        "layer_metrics.idle_host_unnamed_share").read(ctx)
    assert unnamed == pytest.approx(100 * 0.010 / 0.060)


def test_the_counters_metrics_read_the_stats(planes, monkeypatch):
    stats = [{"result_cache_hits": 0, "kernel_calls": 60, "host_reads": 3,
              "backend_compiles": 0,
              "host_ms": {"kernel_call": 12.0}, "host_calls": {
                  "kernel_call": 60}},
             {"result_cache_hits": 0, "kernel_calls": 62, "host_reads": 5,
              "backend_compiles": 1,
              "host_ms": {"kernel_call": 18.5}, "host_calls": {
                  "kernel_call": 62}},
             {"result_cache_hits": 1, "kernel_calls": 0, "host_reads": 0,
              "backend_compiles": 0, "host_ms": {}, "host_calls": {}}]
    ctx = {"requests": [{"info": {"stats": s}} for s in stats]}
    read = lambda name: importlib.import_module(    # noqa: E731
        f"layer_metrics.{name}").read(ctx)
    assert read("kernel_calls_per_q") == pytest.approx(61.0)
    assert read("host_reads_per_q") == pytest.approx(4.0)
    assert read("kernel_call_host_us") == pytest.approx(30500.0 / 122)
    assert read("backend_compiles_in_window") == 1
