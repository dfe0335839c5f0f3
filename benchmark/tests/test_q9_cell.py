"""`sf10-power-q9` (PR 42): the five per-layer metrics it brought, on
hand-made requests and tables — what each computes, and that each returns
nothing, and does not raise, where there is nothing to read (a program
without the composite join's names, the `like_table` activity or the
counters: the parent) — the configuration's fingerprints, the traffic
file, and the cell itself rehearsed at `tiny` on the CPU from a copy to
which the harness took it by files alone (rehearsal.py twins every cell
BENCHMARK.json holds, this one as `tiny-power-q9`)."""

import json
import os

import pytest

import rehearsal
import tpch_columns
import tpch_columns_q9
from reference import load_by_path

CELL = "tiny-power-q9"
ROWS = {"lineitem": 59993741, "orders": 15000000, "partsupp": 8000000,
        "part": 2000000, "supplier": 100000, "nation": 25}
NEW = ("composite_join_device_ms_per_q", "composite_join_hbm_roofline",
       "probe_search_lanes_per_q", "like_table_host_ms_per_q",
       "cross_joins_in_window")


def metric(name):
    return load_by_path("layer_metrics", name)


def request(color="green", t_send=0.0, t_done=10.0, **stats):
    return {"shape": "q9", "params": {"color": color}, "t_send": t_send,
            "t_done": t_done,
            "info": {"stats": {"result_cache_hits": 0, **stats}}}


def traced(requests, by_owner=None, slice_=(0.0, 10.0), rows=ROWS):
    """A context whose trace table is handed in (`trace_programs.table`
    keeps it under this key once it has reduced the xplane)."""
    table = None if by_owner is None else {
        "by_family": {"join": sum(by_owner.values())}, "by_owner": by_owner}
    return {"requests": requests, "slice": slice_, "chips": [0],
            "trace": {"busy_s": 1.0}, "config": {"rows": rows},
            "peaks": {"hbm_bytes_per_s": 819e9}, "_trace_programs": table,
            "shapes": {"q9": load_by_path("queries", "q9")}}


OWNERS = {"join__join_composite/join__probe_lookup": 0.5,
          "join__join_composite/join__probe_expand": 0.2,
          "join__join_composite/join__composite_verify": 0.1,
          "join__join_composite/join__output_gather": 0.1,
          "join__join_prep_composite/join__radix_pass": 0.1,
          "join__uprobe/join__probe_lookup": 4.0,
          "join__join_prep/join__radix_pass": 1.0,
          "aggregate__agg_final/aggregate__segment_reduce": 1.0}
PARENT = {"join__uprobe/join__probe_lookup": 4.0, "join__join/x": 1.0}


def test_composite_ms_sums_the_owners_named_composite():
    read = metric("composite_join_device_ms_per_q").read
    assert read(traced([request(), request()], OWNERS)) \
        == pytest.approx(1e3 * 1.0 / 2)
    # half of a second q9 lies in the slice
    assert read(traced([request(), request(t_send=5.0, t_done=15.0)],
                       OWNERS)) == pytest.approx(1e3 * 1.0 / 1.5)
    # a program without those names (the parent); no table; no query
    assert read(traced([request()], PARENT)) is None
    assert read(traced([request()])) is None
    assert read(traced([], OWNERS)) is None


def test_composite_roofline_is_the_joins_bytes_over_its_programs_time():
    m = metric("composite_join_hbm_roofline")
    share = 183 / 8464
    lines = 59993741 * share
    assert m.join_bytes(ROWS, share) == pytest.approx(
        8000000 * 24 + lines * (56 + 48))
    least_s = m.join_bytes(ROWS, share) / 819e9
    assert m.read(traced([request("almond")], OWNERS)) \
        == pytest.approx(100 * least_s / 1.0)
    assert m.read(traced([request(), request(t_send=5.0, t_done=15.0)],
                         OWNERS)) == pytest.approx(100 * 1.5 * least_s)
    with pytest.raises(ValueError, match="above 100"):
        m.read(traced([request()], {"join__join_composite/x": least_s / 2}))
    # the rows a copy at another scale states: the three tables' alone
    tiny = {"lineitem": 60050, "orders": 15000, "customer": 1500}
    assert m.read(traced([request()], OWNERS, rows=tiny)) > 0
    # the parent's names; no table (a CPU rehearsal); no q9 in the slice
    assert m.read(traced([request()], PARENT)) is None
    assert m.read(traced([request()])) is None
    assert m.read(traced([request(t_send=20.0, t_done=30.0)], OWNERS)) is None
    no_shape = traced([request()], OWNERS)
    del no_shape["shapes"]["q9"]
    assert m.read(no_shape) is None


def test_the_three_counters_metrics_read_the_stats_and_nothing_else():
    lanes = metric("probe_search_lanes_per_q").read
    like = metric("like_table_host_ms_per_q").read
    cross = metric("cross_joins_in_window").read
    new = [request(probe_lookup_lanes_search=8388608, cross_joins=0,
                   like_tables_built=1, host_ms={"like_table": 4.0,
                                                 "kernel_call": 90.0}),
           request(probe_lookup_lanes_search=8388608, cross_joins=0,
                   like_tables_built=1, host_ms={"like_table": 6.0})]
    assert lanes({"requests": new}) == 8388608
    assert like({"requests": new}) == pytest.approx(5.0)
    assert cross({"requests": new}) == 0
    assert cross({"requests": [request(cross_joins=2), new[0]]}) == 2
    # the parent: none of the counters, no such activity
    old = [request(probe_lookup_lanes=1, host_ms={"kernel_call": 90.0}),
           request()]
    for read in (lanes, like, cross):
        assert read({"requests": old}) is None
        assert read({"requests": [{"info": None}]}) is None
        assert read({"requests": []}) is None


def test_the_configurations_fingerprints_rows_and_columns():
    with open(os.path.join(rehearsal.BENCH, "configs",
                           "tpch-sf10-1chip-q9.json")) as f:
        config = json.load(f)
    assert config["data_fingerprint_q9"] \
        == tpch_columns_q9.fingerprint(config["scale_factor"])
    assert config["data_fingerprint"] \
        == tpch_columns.fingerprint(config["scale_factor"])
    assert config["rows"] == ROWS
    assert {**tpch_columns_q9.row_counts(10.0), **ROWS} \
        == tpch_columns_q9.row_counts(10.0)
    columns = [c for cols in config["columns"].values() for c in cols]
    assert sorted(columns) == sorted(config["column_bytes"])
    q9 = load_by_path("queries", "q9")
    assert q9.COLUMNS == config["columns"]
    assert q9.table_rows(ROWS) == ROWS
    assert q9.table_rows({"lineitem": 6, "orders": 1500000}) == {
        "lineitem": 6, "orders": 1500000, "part": 200000,
        "partsupp": 800000, "supplier": 10000, "nation": 25}
    at_rest = q9.needed_bytes(ROWS, config["column_bytes"])
    assert 3.2e9 < at_rest < 3.4e9              # ISSUE 42: 3.3 GB at rest
    with open(os.path.join(rehearsal.BENCH, "configs",
                           "tpch-sf10-1chip-q18-q4.json")) as f:
        assert config["guarantees"] == json.load(f)["guarantees"]
    assert config["reduced"] == ["queries"]
    # what the deployment depends on: a server that lacks one does not
    # start (TrinoServer(requires=...)), and one without the keyword —
    # the commit before PR 42, whose plan of Q9 at SF10 holds a cross
    # join of 4.3e9 rows — fails on the keyword itself
    assert config["server"] == {
        "max_running": 4, "result_cache": True, "scan_cache": True,
        "table_cache": True,
        "requires": ["joins_connected_never_cross", "like_pattern_operand"]}
    with open(os.path.join(rehearsal.ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == config["name"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert "2.4.9" in entry["source"] and "2.4.9.3" in entry["source"]


# ------------------------------------------------------------ the cell


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("bench")))


def test_the_harness_takes_the_cell_by_files_alone(copy):
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "power-q9"
    assert cell["config"] == "tpch-tiny-1chip-q9"
    with open(os.path.join(copy, "benchmark", "traffic",
                           "power-q9.json")) as f:
        traffic = json.load(f)
    assert traffic["loop"] == "closed" and traffic["clients"] == 1
    assert traffic["queue"] == "per_client"
    assert traffic["statement"] == "plain" and traffic["order"] == "sequence"
    assert traffic["shapes"] == [{"shape": "q9", "weight": 1, "per_run": []}]
    assert traffic["law"] == {"kind": "uniform"}
    # no execution limit: the deadline is cooperative and never read
    # inside a compile, so none can end a cold parent in time (PR 41's
    # refusal), and on a slow host one can only fail a sound cold set-up;
    # the parent is turned away by the configuration's `requires`
    assert traffic["session"] == {"result_cache_enabled": "false"}
    assert traffic["prefill_ranks"] == 0
    assert traffic["requests_per_client"] == 2000
    assert traffic["throughput_over"] == "last_completion"
    assert traffic["verify_max_distinct"] == 32
    assert traffic["trace_slice_s"] == 30
    end_to_end = {m["name"] for m in bench["end_to_end"]
                  if CELL in m.get("workloads", [CELL])}
    assert end_to_end == {"throughput_qps", "setup_s"}
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads", [])[:1] == ["sf10-power-q9"]}
    assert mine == set(NEW)
    cells = [c for c in bench["workloads"] if c["name"].startswith("sf")]
    assert len(cells) == 7 and sum(c["chips"] == 4 for c in cells) == 1


def test_traced_run_of_the_cell_at_tiny(copy):
    """--trace 1 on the CPU for 4 seconds: every answer equals the
    reference, every distinct query of the window was compared, COLOR
    differs from request to request, and the counters' metrics are in the result line (the device's own are
    left out: a CPU has no device plane)."""
    proc, last = rehearsal.drive(copy, CELL, 2147483941, 4, 1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 3
    phases = {line["phase"]: line for line in map(
        json.loads, proc.stdout.strip().splitlines()[:-1])}
    verify = phases["verify"]
    assert verify["distinct_in_window"] == last["attempted"]
    assert verify["distinct_checked"] == min(32, verify["distinct_in_window"])
    assert verify["answers_checked"] == verify["distinct_checked"]
    assert set(phases["window"]["by_shape"]) == {"q9"}
    # at `tiny` a COLOR keeps 900 to 1 800 lines, and the probe path's
    # compaction is keyed by the kept count's power of two: a window may
    # meet the other rung once (at SF10 every COLOR's 1.3 M lines, 242 K a
    # buffer, sit 8 % under theirs: tests/test_q9.py)
    assert phases["window"]["compiles_in_window"] <= 1
    got = last["metrics"]
    assert got["cross_joins_in_window"]["value"] == 0
    assert got["probe_search_lanes_per_q"]["value"] == 8192
    assert got["like_table_host_ms_per_q"]["value"] > 0
    assert got["spills_in_window"]["value"] == 0
    assert got["compiles_in_window"]["value"] <= 1
    assert got["host_rss_peak_GB"]["value"] > 0.05
    for name in ("host_staging_mb_per_q", "kernel_calls_per_q",
                 "host_reads_per_q", "backend_compiles_in_window"):
        assert name in got, name
    for name in ("composite_join_device_ms_per_q",
                 "composite_join_hbm_roofline", "query_hbm_roofline",
                 "device_time_attributed_share", "join_device_ms_per_q"):
        assert name not in got


# the parent's constructor: every keyword but `requires`
PARENT_SERVER = '''
import trino_tpu.server.app as app
_init = app.TrinoServer.__init__
def _parent(self, runner, max_running=4, result_cache=True, scan_cache=True,
            table_cache=True, warmup_manifest=None):
    raise AssertionError("the parent's server started")
app.TrinoServer.__init__ = _parent
'''


def test_a_server_without_the_keyword_fails_before_any_table(copy):
    """The cell on a program whose `TrinoServer` has no `requires` (the
    parent): a `TypeError` where run.py constructs the server, a non-zero
    exit, no result line, no table warmed."""
    proc, last = rehearsal.drive(copy, CELL, 12, 2, 0, extra=PARENT_SERVER)
    assert proc.returncode != 0 and last is None
    assert "unexpected keyword argument 'requires'" in proc.stderr
    assert '"phase": "data_load"' not in proc.stdout


# one sum altered where the server encodes q9's rows
TAMPER = '''
import trino_tpu.server.app as app
_encode = app.protocol.encode_rows
def _tampered(rows, types):
    data = _encode(rows, types)
    if data and len(data[0]) == 3 and isinstance(data[0][1], int):
        data[-1][2] = data[-1][2][:-1] + ("1" if data[-1][2][-1] != "1"
                                          else "2")    # a ten-thousandth
    return data
app.protocol.encode_rows = _tampered
'''


def test_a_profit_wrong_in_its_last_digit_comes_out_as_not_correct(copy):
    proc, last = rehearsal.drive(copy, CELL, 11, 2, 0, extra=TAMPER)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert last["correct"] is False
    verify = [json.loads(line) for line in proc.stdout.splitlines()
              if '"phase": "verify"' in line][0]
    assert verify["answers_mismatched"] > 0
    assert verify["first_mismatch"].startswith("q9")
