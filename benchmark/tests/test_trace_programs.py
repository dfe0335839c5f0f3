"""trace_programs.py on the xplane recorded on the v5e by
record_named_trace.py and kept beside it: TPC-H q3 at `tiny` through the
engine, twice, inside the slice annotations, with each run's own
`stats.spans`."""

import json
import os

import pytest

import rehearsal
import trace_programs
import trace_reduce

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
XPLANE = os.path.join(BENCH, "trace_named_sample.xplane.pb")


@pytest.fixture(scope="module")
def sample():
    with open(os.path.join(BENCH, "trace_named_sample.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(sample):
    return trace_programs.reduce(XPLANE, sample["spans"], sample["t_begin"])


def test_the_decoder_agrees_with_profile_data():
    """Every plane's lines and every event's name, start and duration
    as jax's own reader gives them."""
    from jax.profiler import ProfileData
    theirs = ProfileData.from_file(XPLANE)
    mine = {p["name"]: p for p in trace_programs.read_xspace(
        XPLANE, plane_prefixes=("",))}
    events = 0
    for plane in theirs.planes:
        lines = mine[plane.name]["lines"]
        assert [ln.name for ln in plane.lines] == [ln["name"] for ln in lines]
        for theirs_line, line in zip(plane.lines, lines):
            got = line["events"]
            want = list(theirs_line.events)
            assert len(got) == len(want)
            for (meta, start, duration), event in zip(got, want):
                assert meta["name"] == event.name
                assert start == event.start_ns
                assert duration == event.duration_ns
                events += 1
    assert events > 2000


def test_same_file_same_numbers(sample, reduced):
    assert reduced == sample["reduced"]


def test_a_mesh_of_chips_is_the_mean_over_them(sample, reduced):
    """Two chips of which the second has no plane: every second halves,
    the gaps in which no chip ran stay; a cell on another chip reads
    nothing of chip 0's."""
    two = trace_programs.reduce(XPLANE, sample["spans"], sample["t_begin"],
                                chips=(0, 1))
    assert two["busy_s"] == pytest.approx(reduced["busy_s"] / 2)
    assert two["by_family"]["join"] == pytest.approx(
        reduced["by_family"]["join"] / 2)
    assert two["top_ops"][0][:2] == reduced["top_ops"][0][:2]
    assert two["idle_by_span"] == reduced["idle_by_span"]
    assert two["modules"] == reduced["modules"]
    assert trace_programs.reduce(XPLANE, sample["spans"], sample["t_begin"],
                                 chips=(1,)) is None


def test_busy_is_trace_reduces_busy(sample, reduced):
    old = trace_reduce.reduce_xplane(XPLANE, [], sample["t_begin"])
    assert reduced["busy_s"] == pytest.approx(old["busy_s"], rel=1e-9)
    assert reduced["window_s"] == pytest.approx(old["window_s"], rel=1e-9)


def test_families_and_unattributed_add_up_to_busy(reduced):
    families = reduced["by_family"]
    assert set(families) <= set(trace_programs.FAMILIES) | {"unattributed"}
    assert sum(families.values()) == pytest.approx(reduced["busy_s"],
                                                   rel=0.01)
    assert sum(reduced["by_owner"].values()) == pytest.approx(
        sum(families.values()), rel=1e-9)
    # q3: the joins and the scans' filters do the work, a little sorting
    assert families["join"] > families["scan_filter"] > \
        families["aggregate"] > 0 and families["sort"] > 0
    # what is left are the result path's eager slices, a few microseconds
    assert families["unattributed"] < 0.005 * reduced["busy_s"]
    assert {k for k in reduced["by_owner"] if k.startswith("unattributed")} \
        == {"unattributed:dynamic_slice"}


def test_no_program_of_the_engine_is_anonymous(reduced):
    programs = {trace_programs.program_of(m) for m in reduced["modules"]}
    assert not programs & {"run", "op", "prep", "_lambda_", "_lambda"}
    named = {p for p in programs if trace_programs.GRAMMAR.match(p)}
    assert programs - named == {"dynamic_slice"}
    assert {"join__join_prep", "join__uprobe", "join__uattach",
            "aggregate__agg_final", "sort__topn_masked"} <= named


def test_owners_are_program_and_innermost_scope(reduced):
    owners = reduced["by_owner"]
    # a kernel several operators share is the calling operator's
    assert owners["join__join_prep/join__radix_pass"] > 0
    assert owners["aggregate__agg_final/aggregate__radix_pass"] > 0
    assert owners["sort__topn_masked/sort__radix_pass"] > 0
    assert owners["join__probe_compact/join__compact_gather"] > 0
    assert owners["scan_filter__chain_filter/scan_filter__compact_gather"] > 0
    for owner in owners:
        if not owner.startswith("unattributed:"):
            program, scope = owner.split("/")
            assert trace_programs.GRAMMAR.match(program), owner
            assert scope == "-" or trace_programs.GRAMMAR.match(scope)


def test_owner_rule():
    own = trace_programs.owner_of
    module = "jit_join__uprobe(123)"
    assert own("jit(join__uprobe)/jit(main)/join__probe_lookup/"
               "join__mxu_lookup/while/body/dot_general:", module) \
        == ("join__uprobe/join__mxu_lookup", "join")
    assert own("jit(join__uprobe)/jit(main)/mul:", module) \
        == ("join__uprobe/-", "join")
    assert own("", module) == ("join__uprobe/-", "join")
    # a scope outranks the program it sits in
    assert own("jit(x)/aggregate__agg_partial/aggregate__radix_pass/sort:",
               "jit_exchange__mesh_prog(9)") \
        == ("exchange__mesh_prog/aggregate__radix_pass", "aggregate")
    # no scope and no named module: nobody's
    assert own("jit(dynamic_slice)/dynamic_slice:", "jit_dynamic_slice(7)") \
        == ("unattributed:dynamic_slice", "unattributed")
    # an op outside every module event, with nothing of the grammar in
    # its path, is unattributed whatever its path's root says
    assert own("jit(run)/jit(main)/add:", "") \
        == ("unattributed:?", "unattributed")
    assert own("jit(join__uprobe)/jit(main)/mul:", "")[1] == "unattributed"


def test_an_op_outside_every_module_event_is_unattributed(sample):
    """The same file with the `XLA Modules` line taken for empty: ops
    whose path has a scope keep it, the rest are nobody's."""
    planes = trace_programs.read_xspace(XPLANE)
    real = trace_programs.read_xspace
    for plane in planes:
        for line in plane["lines"]:
            if line["name"] == trace_programs.MODULES_LINE:
                line["events"] = []
    trace_programs.read_xspace = lambda path: planes
    try:
        bare = trace_programs.reduce(XPLANE, sample["spans"],
                                     sample["t_begin"])
    finally:
        trace_programs.read_xspace = real
    assert bare["modules"] == []
    assert bare["by_family"]["unattributed"] > \
        sample["reduced"]["by_family"]["unattributed"]
    assert not any(k.endswith("/-") for k in bare["by_owner"])
    assert bare["by_owner"]["join__join_prep/join__radix_pass"] == \
        sample["reduced"]["by_owner"]["join__join_prep/join__radix_pass"]


def test_idle_is_laid_on_the_requests_spans(sample, reduced):
    idle = reduced["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(reduced["idle_s"], rel=1e-6)
    # three 20 ms sleeps (and the queue's 5 ms) with nothing running, the
    # rest inside `execution`: host dispatch between tiny kernels
    assert 0.06 < idle["no_request"] < 0.1
    assert idle["dispatch"] > idle["result_fetch"] > 0
    # shift the requests by a second: every gap is nobody's
    moved = trace_programs.reduce(XPLANE, sample["spans"],
                                  sample["t_begin"] + 1.0)
    assert set(moved["idle_by_span"]) == {"no_request"}


def test_self_time_of_execution_is_dispatch():
    spans = [("queued", 0.0, 1.0), ("planning", 1.0, 1.5),
             ("execution", 1.5, 9.0), ("compile", 2.0, 4.0),
             ("compile", 5.0, 5.5), ("result_fetch", 8.0, 9.0)]
    assert trace_programs.self_time_spans(spans) == [
        ("queued", 0.0, 1.0), ("planning", 1.0, 1.5),
        ("dispatch", 1.5, 2.0), ("dispatch", 4.0, 5.0),
        ("dispatch", 5.5, 8.0), ("compile", 2.0, 4.0),
        ("compile", 5.0, 5.5), ("result_fetch", 8.0, 9.0)]


def test_metrics_read_the_table(sample, reduced, tmp_path, monkeypatch):
    """The eight layer-metric files on a hand-made ctx around the sample:
    two executed queries wholly inside the slice, one hit."""
    import reference
    t0 = sample["t_begin"]

    def request(spans, queued_ms, staged, hits=0):
        return {"t_send": t0 + 0.01, "t_done": t0 + 0.1, "info": {"stats": {
            "result_cache_hits": hits, "spans": spans,
            "queued_ms": queued_ms, "scan_host_staging_bytes": staged}}}
    ctx = {"requests": [request(sample["spans"][0], 5.0, 0),
                        request(sample["spans"][1], 7.0, 4_000_000),
                        request([], 0, 0, hits=1)],
           "trace": {"busy_s": reduced["busy_s"]},
           "slice": (t0, t0 + reduced["window_s"]),
           "_trace_programs": reduced}
    read = lambda name: reference.load_by_path(  # noqa: E731
        "layer_metrics", name).read(ctx)
    assert read("device_time_attributed_share") == pytest.approx(
        100 * (1 - reduced["by_family"]["unattributed"]
               / sum(reduced["by_family"].values())))
    assert read("device_time_attributed_share") > 99.8
    for family in ("scan_filter", "aggregate", "join", "sort"):
        assert read(f"{family}_device_ms_per_q") == pytest.approx(
            1e3 * reduced["by_family"][family] / 2)
    assert read("queue_wait_p95_ms") == 7.0
    assert read("host_staging_mb_per_q") == 2.0
    idle = reduced["idle_by_span"]
    assert read("idle_unattributed_share") == pytest.approx(
        100 * idle["no_request"] / sum(idle.values()))


NEW = {"device_time_attributed_share", "scan_filter_device_ms_per_q",
       "aggregate_device_ms_per_q", "join_device_ms_per_q",
       "sort_device_ms_per_q", "idle_unattributed_share",
       "queue_wait_p95_ms", "host_staging_mb_per_q"}


def test_a_program_without_names_or_spans_gives_nones(sample, reduced):
    """The parent commit under these files: no `spans`, `queued_ms` or
    `scan_host_staging_bytes` in the stats and no grammar name in the
    trace. Every new metric is left out; none raises."""
    import reference
    t0 = sample["t_begin"]
    anonymous = dict(reduced, by_family={"unattributed": reduced["busy_s"]},
                     idle_by_span={"no_request": reduced["idle_s"]})
    ctx = {"requests": [{"t_send": t0, "t_done": t0 + 0.1, "info": {
        "stats": {"result_cache_hits": 0}}}],
        "trace": {"busy_s": reduced["busy_s"]},
        "slice": (t0, t0 + reduced["window_s"]),
        "_trace_programs": anonymous}
    for name in sorted(NEW):
        assert reference.load_by_path("layer_metrics", name).read(ctx) \
            is None, name


def test_the_cpu_rehearsal_leaves_the_device_metrics_out(tmp_path):
    """--trace 1 on the CPU: no device plane, so the six metrics read
    from the trace are `None` and left out (no CPU number under a device
    metric's name, and no error); the span's and the counter's are there."""
    copy = rehearsal.make_copy(str(tmp_path))
    proc, last = rehearsal.drive(copy, "tiny-dashboard", 7, 2, 1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert last["correct"] is True
    got = set(last["metrics"]) & NEW
    assert got == {"queue_wait_p95_ms", "host_staging_mb_per_q"}
    assert last["metrics"]["queue_wait_p95_ms"]["value"] >= 0
    assert last["metrics"]["host_staging_mb_per_q"]["value"] >= 0
    assert trace_programs.reduce(
        trace_programs.newest_xplane(os.path.join(copy, ".bench_out")),
        [], 0.0) is None
