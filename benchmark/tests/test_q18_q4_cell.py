"""`sf10-power-q18-q4` (PR 37): the four per-layer metrics it brought, on
hand-made requests and tables — what each computes, and that each returns
nothing, and does not raise, where there is nothing to read — and the cell
itself rehearsed at `tiny` on the CPU from a copy to which the harness took
it by files alone (rehearsal.py twins every cell BENCHMARK.json holds, this
one as `tiny-power-q18-q4`). At `tiny` no order reaches QUANTITY 312..315:
q18's answers there are empty on both sides, q4's are not
(tests/test_q18_q4.py sends a QUANTITY that `tiny` reaches)."""

import json
import os

import pytest

import rehearsal
import tpch_columns
import tpch_columns_q18_q4
from reference import load_by_path

CELL = "tiny-power-q18-q4"
ROWS = {"lineitem": 59993741, "orders": 15000000, "customer": 1500000}


def metric(name):
    return load_by_path("layer_metrics", name)


def request(shape="q18", t_send=0.0, t_done=10.0, **stats):
    return {"shape": shape, "t_send": t_send, "t_done": t_done,
            "info": {"stats": {"result_cache_hits": 0, **stats}}}


def traced(requests, by_family=None, by_owner=None, slice_=(0.0, 10.0)):
    """A context whose trace table is handed in (`trace_programs.table`
    keeps it under this key once it has reduced the xplane)."""
    table = None if by_family is None else {
        "by_family": by_family, "by_owner": by_owner or {}}
    return {"requests": requests, "slice": slice_, "chips": [0],
            "trace": {"busy_s": 1.0}, "config": {"rows": ROWS},
            "peaks": {"hbm_bytes_per_s": 819e9}, "_trace_programs": table}


def test_groupby_roofline_is_q18s_bytes_over_the_aggregate_familys_time():
    m = metric("groupby_hbm_roofline")
    assert m.groupby_bytes(ROWS) == (59993741 + 15000000) * 16
    least_s = m.groupby_bytes(ROWS) / 819e9
    one = traced([request("q18"), request("q4")], {"aggregate": 2.0})
    assert m.read(one) == pytest.approx(100 * least_s / 2.0)
    # half of a second q18 lies in the slice; q4 has no such GROUP BY
    more = traced([request("q18"), request("q18", 5.0, 15.0),
                   request("q4")], {"aggregate": 3.0})
    assert m.read(more) == pytest.approx(100 * 1.5 * least_s / 3.0)
    with pytest.raises(ValueError, match="above 100"):
        m.read(traced([request("q18")], {"aggregate": least_s / 2}))
    # no q18 in the slice, no aggregate time, no table (a CPU rehearsal)
    assert m.read(traced([request("q4")], {"aggregate": 1.0})) is None
    assert m.read(traced([request("q18")], {"join": 1.0})) is None
    assert m.read(traced([request("q18")])) is None


def test_semijoin_ms_sums_the_semi_and_mark_owners():
    read = metric("semijoin_device_ms_per_q").read
    owners = {"join__semijoin/join__semi_probe": 0.2,
              "join__semijoin/join__compact_shift": 0.1,
              "join__semijoin_prep/join__radix_pass": 0.3,
              "join__markjoin/join__mark_probe": 0.05,
              "join__semijoin_dense_table/join__semi_build": 0.05,
              "join__uprobe/join__probe_lookup": 4.0,
              "aggregate__agg_final/aggregate__segment_reduce": 1.0}
    two = traced([request("q18"), request("q4")], {"join": 4.7}, owners)
    assert read(two) == pytest.approx(1e3 * 0.7 / 2)
    # a program without those names (an inner join alone); no table
    inner = {"join__uprobe/join__probe_lookup": 4.0}
    assert read(traced([request("q18")], {"join": 4.0}, inner)) is None
    assert read(traced([request("q18")])) is None


def test_spills_counts_fallbacks_and_queries_that_spilled():
    read = metric("spills_in_window").read
    clean = request(spilled_bytes=0, spill_fallbacks=0)
    assert read({"requests": [clean, clean]}) == 0
    hurt = [request(spilled_bytes=1 << 30, spill_fallbacks=2), clean,
            request(spilled_bytes=5, spill_fallbacks=0)]
    assert read({"requests": hurt}) == 4
    assert read({"requests": [request()]}) is None
    assert read({"requests": [{"info": None}]}) is None
    assert read({"requests": []}) is None


def test_host_rss_is_this_process_s_peak():
    got = metric("host_rss_peak_GB").read({})
    assert 0.01 < got < 64.0


def test_the_new_columns_fingerprint_is_the_configuration_s():
    with open(os.path.join(rehearsal.BENCH, "configs",
                           "tpch-sf10-1chip-q18-q4.json")) as f:
        config = json.load(f)
    assert config["data_fingerprint_q18_q4"] \
        == tpch_columns_q18_q4.fingerprint(config["scale_factor"])
    assert config["data_fingerprint"] \
        == tpch_columns.fingerprint(config["scale_factor"])
    assert config["rows"] == tpch_columns.row_counts(10.0) == ROWS
    columns = [c for cols in config["columns"].values() for c in cols]
    assert sorted(columns) == sorted(config["column_bytes"])
    for shape in ("q18", "q4"):
        for table, cols in load_by_path("queries", shape).COLUMNS.items():
            assert set(cols) <= set(config["columns"][table]), shape


# ------------------------------------------------------------ the cell


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("bench")))


def test_the_harness_takes_the_cell_by_files_alone(copy):
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "power-q18-q4"
    assert cell["config"] == "tpch-tiny-1chip-q18-q4"
    with open(os.path.join(copy, "benchmark", "traffic",
                           "power-q18-q4.json")) as f:
        traffic = json.load(f)
    assert traffic["loop"] == "closed" and traffic["clients"] == 1
    assert traffic["order"] == "sequence"
    assert [s["shape"] for s in traffic["shapes"]] == ["q18", "q4"]
    assert all(s["per_run"] == [] for s in traffic["shapes"])
    assert traffic["session"] == {"result_cache_enabled": "false"}
    end_to_end = {m["name"] for m in bench["end_to_end"]
                  if CELL in m.get("workloads", [CELL])}
    assert end_to_end == {"throughput_qps", "setup_s"}
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads", [])[:1] == ["sf10-power-q18-q4"]}
    assert mine == {"groupby_hbm_roofline", "semijoin_device_ms_per_q",
                    "spills_in_window", "host_rss_peak_GB"}


def test_traced_run_of_the_cell_at_tiny(copy):
    """--trace 1 on the CPU for 3 seconds: every answer equals the
    reference, every distinct query of the window was compared, nothing
    compiled or spilled in the window, and the counters' metrics are in
    the result line (the device's own are left out: a CPU has no device
    plane)."""
    proc, last = rehearsal.drive(copy, CELL, 2147483929, 3, 1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 4 and last["attempted"] % 2 == 0
    phases = {line["phase"]: line for line in map(
        json.loads, proc.stdout.strip().splitlines()[:-1])}
    verify = phases["verify"]
    assert verify["distinct_checked"] == min(16, verify["distinct_in_window"])
    assert verify["answers_checked"] >= verify["distinct_checked"]
    assert set(phases["window"]["by_shape"]) == {"q18", "q4"}
    assert phases["window"]["compiles_in_window"] == 0
    got = last["metrics"]
    assert got["spills_in_window"]["value"] == 0
    assert got["compiles_in_window"]["value"] == 0
    assert got["host_rss_peak_GB"]["value"] > 0.05
    assert "host_staging_mb_per_q" in got
    for name in ("groupby_hbm_roofline", "semijoin_device_ms_per_q",
                 "query_hbm_roofline", "device_time_attributed_share"):
        assert name not in got


# one count altered where the server encodes q4's rows
TAMPER = '''
import trino_tpu.server.app as app
_encode = app.protocol.encode_rows
def _tampered(rows, types):
    data = _encode(rows, types)
    if data and len(data[0]) == 2 and isinstance(data[0][0], str):
        data[0][1] += 1                        # order_count of one priority
    return data
app.protocol.encode_rows = _tampered
'''


def test_a_wrong_count_in_q4_comes_out_as_not_correct(copy):
    proc, last = rehearsal.drive(copy, CELL, 11, 2, 0, extra=TAMPER)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert last["correct"] is False
    verify = [json.loads(line) for line in proc.stdout.splitlines()
              if '"phase": "verify"' in line][0]
    assert verify["answers_mismatched"] > 0
    assert verify["first_mismatch"].startswith("q4")
