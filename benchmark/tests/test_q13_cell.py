"""`sf10-power-q13` (PR 44): the three per-layer metrics it brought, on
hand-made requests and tables — what each computes, and that each returns
nothing, and does not raise, where there is nothing to read (a program
without the outer join's names or the rerun counter: the parent) — the
configuration's fingerprints, the traffic file's sixteen word pairs, and
the cell itself rehearsed at `tiny` on the CPU from a copy to which the
harness took it by files alone (rehearsal.py twins every cell
BENCHMARK.json holds, this one as `tiny-power-q13`)."""

import itertools
import json
import os

import pytest

import rehearsal
import tpch_columns
import tpch_columns_q13
import traffic_gen
from reference import load_by_path

CELL = "tiny-power-q13"
ROWS = {"customer": 1500000, "orders": 15000000}
NEW = ("outer_join_device_ms_per_q", "outer_join_hbm_roofline",
       "probe_reruns_per_q")


def metric(name):
    return load_by_path("layer_metrics", name)


def request(word1="special", word2="packages", t_send=0.0, t_done=10.0,
            **stats):
    return {"shape": "q13", "params": {"word1": word1, "word2": word2},
            "t_send": t_send, "t_done": t_done,
            "info": {"stats": {"result_cache_hits": 0, **stats}}}


def traced(requests, by_owner=None, slice_=(0.0, 10.0), rows=ROWS):
    """A context whose trace table is handed in (`trace_programs.table`
    keeps it under this key once it has reduced the xplane)."""
    table = None if by_owner is None else {
        "by_family": {"join": sum(by_owner.values())}, "by_owner": by_owner}
    return {"requests": requests, "slice": slice_, "chips": [0],
            "trace": {"busy_s": 1.0}, "config": {"rows": rows},
            "peaks": {"hbm_bytes_per_s": 819e9}, "_trace_programs": table,
            "shapes": {"q13": load_by_path("queries", "q13")}}


OWNERS = {"join__join_outer/join__probe_lookup": 0.4,
          "join__join_outer/join__probe_expand": 0.8,
          "join__join_outer/join__outer_fill": 0.1,
          "join__join_outer/join__output_gather": 0.5,
          "join__join_prep_outer/join__radix_pass": 0.15,
          "join__dense_table_outer/join__build_dense_table": 0.05,
          "join__uprobe/join__probe_lookup": 4.0,
          "join__join_prep/join__radix_pass": 1.0,
          "aggregate__agg_final/aggregate__segment_reduce": 1.0}
# the parent runs the same join under an INNER join's names
PARENT = {"join__join/join__probe_expand": 0.9,
          "join__join/join__output_gather": 0.5,
          "join__join_prep/join__radix_pass": 0.15,
          "join__dense_table/join__build_dense_table": 0.05}


def test_outer_ms_sums_the_owners_named_outer():
    read = metric("outer_join_device_ms_per_q").read
    assert read(traced([request(), request()], OWNERS)) \
        == pytest.approx(1e3 * 2.0 / 2)
    # half of a second q13 lies in the slice
    assert read(traced([request(), request(t_send=5.0, t_done=15.0)],
                       OWNERS)) == pytest.approx(1e3 * 2.0 / 1.5)
    # a scope alone, inside a program of another name (the mesh's)
    assert read(traced([request()], {
        "exchange__mesh_prog/join__outer_fill": 0.25,
        "exchange__mesh_prog/join__probe_expand": 3.0})) \
        == pytest.approx(250.0)
    # a program without those names (the parent); no table; no query
    assert read(traced([request()], PARENT)) is None
    assert read(traced([request()])) is None
    assert read(traced([], OWNERS)) is None


def test_outer_roofline_is_the_joins_bytes_over_its_programs_time():
    m = metric("outer_join_hbm_roofline")
    q13 = load_by_path("queries", "q13")
    kept = q13.kept_orders(15000000, {"word1": "special",
                                      "word2": "packages"})
    assert kept == pytest.approx(15000000 * (1 - 8 / 2048))
    joined = kept + 500000
    assert m.join_bytes(ROWS, kept) == pytest.approx(
        1500000 * 8 + kept * 16 + joined * 17)
    least_s = m.join_bytes(ROWS, kept) / 819e9
    assert 0.0005 < least_s < 0.001             # 0.5 GB: under a millisecond
    assert m.read(traced([request()], OWNERS)) \
        == pytest.approx(100 * least_s / 2.0)
    assert m.read(traced([request(), request(t_send=5.0, t_done=15.0)],
                         OWNERS)) == pytest.approx(100 * 1.5 * least_s / 2.0)
    # another pair keeps another share of the orders
    other = m.read(traced([request("pending", "packages")], OWNERS))
    assert other > m.read(traced([request()], OWNERS))
    with pytest.raises(ValueError, match="above 100"):
        m.read(traced([request()], {"join__join_outer/x": least_s / 2}))
    # the rows a copy at another scale states
    tiny = {"lineitem": 60050, "orders": 15000, "customer": 1500}
    assert m.read(traced([request()], OWNERS, rows=tiny)) > 0
    # the parent's names; no table (a CPU rehearsal); no q13 in the slice
    assert m.read(traced([request()], PARENT)) is None
    assert m.read(traced([request()])) is None
    assert m.read(traced([request(t_send=20.0, t_done=30.0)],
                         OWNERS)) is None
    no_shape = traced([request()], OWNERS)
    del no_shape["shapes"]["q13"]
    assert m.read(no_shape) is None
    no_peaks = traced([request()], OWNERS)
    no_peaks["peaks"] = None
    assert m.read(no_peaks) is None


def test_the_rerun_metric_reads_the_counter_and_nothing_else():
    read = metric("probe_reruns_per_q").read
    new = [request(probe_overflow_reruns=1), request(probe_overflow_reruns=1),
           request(probe_overflow_reruns=0)]
    assert read({"requests": new}) == pytest.approx(2 / 3)
    assert read({"requests": [request(probe_overflow_reruns=0)]}) == 0
    # the parent: no such counter
    old = [request(probe_lookup_lanes=1), request()]
    assert read({"requests": old}) is None
    assert read({"requests": [{"info": None}]}) is None
    assert read({"requests": []}) is None
    # a result-cache hit ran nothing
    hit = request(probe_overflow_reruns=5)
    hit["info"]["stats"]["result_cache_hits"] = 1
    assert read({"requests": [hit]}) is None


def test_the_configurations_fingerprints_rows_and_columns():
    with open(os.path.join(rehearsal.BENCH, "configs",
                           "tpch-sf10-1chip-q13.json")) as f:
        config = json.load(f)
    assert config["data_fingerprint_q13"] \
        == tpch_columns_q13.fingerprint(config["scale_factor"])
    assert config["data_fingerprint"] \
        == tpch_columns.fingerprint(config["scale_factor"])
    assert config["rows"] == ROWS
    columns = [c for cols in config["columns"].values() for c in cols]
    assert sorted(columns) == sorted(config["column_bytes"])
    assert config["column_bytes"] == {"c_custkey": 8, "o_orderkey": 8,
                                      "o_custkey": 8, "o_comment": 4}
    q13 = load_by_path("queries", "q13")
    assert q13.COLUMNS == config["columns"]
    assert q13.table_rows(ROWS) == ROWS
    at_rest = q13.needed_bytes(ROWS, config["column_bytes"])
    assert at_rest == 15000000 * 20 + 1500000 * 8      # 0.31 GB at rest
    with open(os.path.join(rehearsal.BENCH, "configs",
                           "tpch-sf10-1chip-q9.json")) as f:
        assert config["guarantees"] == json.load(f)["guarantees"]
    assert config["reduced"] == ["queries"]
    assert list(config["reduced_why"]) == ["queries"]
    # what the deployment depends on: the parent serves Q13 (step 0), so
    # only what Q13 uses of PR 42's handshake is named
    assert config["server"] == {
        "max_running": 4, "result_cache": True, "scan_cache": True,
        "table_cache": True, "requires": ["like_pattern_operand"]}
    with open(os.path.join(rehearsal.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert "2.4.13" in entry["source"] and "2.4.13.3" in entry["source"]
    assert entry["reduced"] == ["queries"]
    assert bench["configs"][-1] is entry
    assert bench["workloads"][-1]["name"] == "sf10-power-q13"
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(NEW)
    for text in (entry["why"], bench["workloads"][-1]["why"]):
        assert 0 < len(text) <= 200 and "\t" not in text


def test_the_traffic_draws_both_words_per_request_over_sixteen_pairs():
    traffic = traffic_gen.load_traffic("power-q13")
    q13 = load_by_path("queries", "q13")
    assert q13.DOMAIN == {
        "word1": ["special", "pending", "unusual", "express"],
        "word2": ["packages", "requests", "accounts", "deposits"]}
    pairs = set(itertools.product(q13.DOMAIN["word1"], q13.DOMAIN["word2"]))
    assert len(pairs) == 16
    for seed in (1, 2147483659, 2100004401):
        plan = traffic_gen.make_plan(traffic, seed, 51)
        assert plan["per_run"] == {"q13": {}}       # nothing pinned a run
        assert len(plan["clients"]) == 1
        stream = [(p["word1"], p["word2"])
                  for shape, p in plan["clients"][0][:64]]
        assert set(stream) == pairs
        # uniform: each pair 3 to 5 times in 64 draws (the golden-ratio
        # offsets walk the law's mass), whatever the seed
        assert {stream.count(pair) for pair in pairs} <= {3, 4, 5}
        sql = q13.SQL.format(**plan["clients"][0][0][1])
        assert "NOT LIKE '%{}%{}%'".format(*stream[0]) in sql


# ------------------------------------------------------------ the cell


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("bench")))


def test_the_harness_takes_the_cell_by_files_alone(copy):
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "power-q13"
    assert cell["config"] == "tpch-tiny-1chip-q13"
    with open(os.path.join(copy, "benchmark", "traffic",
                           "power-q13.json")) as f:
        traffic = json.load(f)
    assert traffic["loop"] == "closed" and traffic["clients"] == 1
    assert traffic["queue"] == "per_client"
    assert traffic["statement"] == "plain" and traffic["order"] == "sequence"
    assert traffic["shapes"] == [{"shape": "q13", "weight": 1,
                                  "per_run": []}]
    assert traffic["law"] == {"kind": "uniform"}
    # no execution limit (PR 42's reasons: the deadline is cooperative and
    # never read inside a compile)
    assert traffic["session"] == {"result_cache_enabled": "false"}
    assert traffic["prefill_ranks"] == 0
    assert traffic["requests_per_client"] == 2000
    assert traffic["throughput_over"] == "last_completion"
    assert traffic["verify_max_distinct"] == 16
    assert traffic["trace_slice_s"] == 30
    end_to_end = {m["name"] for m in bench["end_to_end"]
                  if CELL in m.get("workloads", [CELL])}
    assert end_to_end == {"throughput_qps", "setup_s"}
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads", [])[:1] == ["sf10-power-q13"]}
    assert mine == set(NEW)
    listed = {m["name"] for m in bench["per_layer"]
              if "sf10-power-q13" in m.get("workloads", [])}
    assert listed == set(NEW) | {
        "query_hbm_roofline", "device_time_attributed_share",
        "scan_filter_device_ms_per_q", "aggregate_device_ms_per_q",
        "join_device_ms_per_q", "sort_device_ms_per_q",
        "idle_unattributed_share", "host_staging_mb_per_q",
        "spills_in_window", "host_rss_peak_GB", "idle_host_unnamed_share",
        "kernel_calls_per_q", "host_reads_per_q",
        "backend_compiles_in_window", "like_table_host_ms_per_q",
        "probe_search_lanes_per_q"}
    cells = [c for c in bench["workloads"] if c["name"].startswith("sf")]
    assert sum(c["chips"] == 4 for c in cells) == 1 <= len(cells) // 2


def test_traced_run_of_the_cell_at_tiny(copy):
    """--trace 1 on the CPU for 4 seconds: every answer equals the
    reference, every distinct query of the window was compared, the word
    pairs differ from request to request, and the counters' metrics are in
    the result line (the device's own are left out: a CPU has no device
    plane). At `tiny` no pair crosses a capacity rung of an ENGINE
    program — 1 500 customers in 2 048 lanes, 14 919 to 14 993 kept orders
    in 16 384, 15 419 to 15 493 joined rows in 16 384 — so
    `compiles_in_window` is 0; the answer has 33 to 35 rows, and the
    result's eager `x[:n]` compiles once a length (`jit(dynamic_slice)`,
    ROADMAP B4: `backend_compiles_in_window` may read a few here; at SF10
    the driver's warm cache holds them, PERF.md §6)."""
    proc, last = rehearsal.drive(copy, CELL, 2147484413, 4, 1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 3
    phases = {line["phase"]: line for line in map(
        json.loads, proc.stdout.strip().splitlines()[:-1])}
    verify = phases["verify"]
    assert verify["distinct_in_window"] == min(16, last["attempted"])
    assert verify["distinct_checked"] == verify["distinct_in_window"]
    assert verify["answers_checked"] == last["attempted"]
    assert set(phases["window"]["by_shape"]) == {"q13"}
    assert phases["window"]["compiles_in_window"] == 0
    got = last["metrics"]
    assert got["probe_reruns_per_q"]["value"] == 0
    assert got["probe_search_lanes_per_q"]["value"] == 0
    assert got["like_table_host_ms_per_q"]["value"] > 0
    assert got["spills_in_window"]["value"] == 0
    assert got["compiles_in_window"]["value"] == 0
    assert got["host_rss_peak_GB"]["value"] > 0.05
    for name in ("host_staging_mb_per_q", "kernel_calls_per_q",
                 "host_reads_per_q", "backend_compiles_in_window"):
        assert name in got, name
    for name in ("outer_join_device_ms_per_q", "outer_join_hbm_roofline",
                 "query_hbm_roofline", "device_time_attributed_share",
                 "join_device_ms_per_q"):
        assert name not in got


# the parent's collector: no rerun counter in the snapshot
PARENT_STATS = '''
import trino_tpu.obs.stats as stats
_snapshot = stats.QueryStatsCollector.snapshot
def _parent(self, *a, **k):
    out = _snapshot(self, *a, **k)
    out.pop("probe_overflow_reruns", None)
    return out
stats.QueryStatsCollector.snapshot = _parent
'''


def test_a_program_without_the_counter_leaves_the_metric_out(copy):
    """The cell on a program whose stats lack `probe_overflow_reruns` (the
    parent): a whole, correct line without `probe_reruns_per_q`."""
    proc, last = rehearsal.drive(copy, CELL, 12, 2, 1, extra=PARENT_STATS)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert last["correct"] is True
    assert "probe_reruns_per_q" not in last["metrics"]
    assert "kernel_calls_per_q" in last["metrics"]


# one count altered where the server encodes q13's rows
TAMPER = '''
import trino_tpu.server.app as app
_encode = app.protocol.encode_rows
def _tampered(rows, types):
    data = _encode(rows, types)
    if data and len(data[0]) == 2 and data[0][0] == 0:
        data[0][1] += 1                 # one more customer without orders
    return data
app.protocol.encode_rows = _tampered
'''


def test_one_null_extended_row_too_many_comes_out_as_not_correct(copy):
    proc, last = rehearsal.drive(copy, CELL, 11, 2, 0, extra=TAMPER)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert last["correct"] is False
    verify = [json.loads(line) for line in proc.stdout.splitlines()
              if '"phase": "verify"' in line][0]
    assert verify["answers_mismatched"] > 0
    assert verify["first_mismatch"].startswith("q13")
