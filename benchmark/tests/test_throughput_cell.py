"""`sf10-throughput-s3` (PR 34): the two per-layer metrics it brought, on
hand-made requests — what each computes, and that each returns nothing,
and does not raise, where the program has no such span or counter (the
parent commit) — and the cell itself rehearsed at `tiny` on the CPU from
a copy to which the harness took it by files alone (rehearsal.py twins
every cell BENCHMARK.json holds, this one as `tiny-throughput-s3`)."""

import json
import os

import pytest

import rehearsal
from reference import load_by_path

CELL = "tiny-throughput-s3"


def metric(name):
    return load_by_path("layer_metrics", name)


def request(shape="q3", t_send=0.0, t_done=10.0, latency_s=None, **stats):
    return {"shape": shape, "t_send": t_send, "t_done": t_done,
            "latency_s": t_done - t_send if latency_s is None else latency_s,
            "info": {"stats": {"result_cache_hits": 0, **stats}}}


COUNTERS = {"memory_kills": 0, "device_oom_errors": 0}


def test_queries_in_flight_is_the_time_average_of_covering_executions():
    read = metric("queries_in_flight_mean").read
    both = [request(spans=[["execution", 0.0, 10.0]]),
            request(spans=[["execution", 0.0, 10.0]])]
    assert read({"requests": both}) == 2.0      # over their overlap
    # one after another: the server serialises
    serial = [request(t_send=0.0, t_done=10.0,
                      spans=[["queued", 0.0, 0.0], ["execution", 0.0, 10.0]]),
              request(t_send=0.0, t_done=20.0,
                      spans=[["queued", 0.0, 10.0],
                             ["execution", 10.0, 20.0]])]
    assert read({"requests": serial}) == 1.0
    # half overlapped; what lies outside first send .. last answer is cut
    half = [request(t_send=0.0, t_done=10.0,
                    spans=[["execution", -5.0, 10.0]]),
            request(t_send=5.0, t_done=15.0,
                    spans=[["execution", 5.0, 15.0]])]
    assert read({"requests": half}) == pytest.approx(20.0 / 15.0)
    # a program without spans, a run without requests
    assert read({"requests": [request()]}) is None
    assert read({"requests": [request(spans=[])]}) is None
    assert read({"requests": []}) is None


def test_memory_kills_sums_the_killers_victims_and_device_refusals():
    read = metric("memory_kills_in_window").read
    clean = request(**COUNTERS)
    assert read({"requests": [clean, clean]}) == 0
    hurt = [request(**{**COUNTERS, "memory_kills": 1}),
            request(**{**COUNTERS, "device_oom_errors": 2}), clean]
    assert read({"requests": hurt}) == 3
    # the parent has no such counters; a request without its info
    assert read({"requests": [request()]}) is None
    assert read({"requests": [{"info": None}]}) is None
    assert read({"requests": []}) is None


# ------------------------------------------------------------ the cell


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("bench")))


def test_the_harness_takes_the_cell_by_files_alone(copy):
    """BENCHMARK.json's entries and the files they name: the
    configuration's shape at `tiny`, three clients, the cell's metrics."""
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "throughput-s3"
    assert cell["config"] == "tpch-tiny-1chip-s3"
    with open(os.path.join(copy, "benchmark", "configs",
                           "tpch-tiny-1chip-s3.json")) as f:
        config = json.load(f)
    assert config["schema"] == "tiny" and config["chips"] == 1
    assert config["reduced"] == ["queries", "refresh_stream", "stream_order"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert len(config["guarantees"]) == 5
    with open(os.path.join(copy, "benchmark", "traffic",
                           "throughput-s3.json")) as f:
        traffic = json.load(f)
    assert traffic["clients"] == 3 and traffic["queue"] == "per_client"
    assert [s["shape"] for s in traffic["shapes"]] == ["q6", "q1", "q3"]
    assert traffic["verify_max_distinct"] == 32
    end_to_end = {m["name"] for m in bench["end_to_end"]
                  if CELL in m.get("workloads", [CELL])}
    assert end_to_end == {"throughput_qps", "setup_s"}


def test_traced_run_of_the_cell_at_tiny(copy):
    """--trace 1 on the CPU, three streams for 3 seconds: every answer
    equals the reference, every distinct query of the window was
    compared, and the two new metrics and the queue wait are in the
    result line (the device's own are left out: a CPU has no device
    plane)."""
    proc, last = rehearsal.drive(copy, CELL, 2147483929, 3, 1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 9 and last["attempted"] % 3 == 0
    phases = {line["phase"]: line for line in map(
        json.loads, proc.stdout.strip().splitlines()[:-1])}
    verify = phases["verify"]
    # a 3 s window at `tiny` holds more distinct queries than the 18 of a
    # 51 s window at SF10: all of them up to the traffic's 32 are compared
    assert verify["distinct_checked"] == min(32, verify["distinct_in_window"])
    assert verify["answers_checked"] >= verify["distinct_checked"]
    assert set(phases["window"]["by_shape"]) == {"q6", "q1", "q3"}
    assert phases["window"]["compiles_in_window"] == 0
    got = last["metrics"]
    assert got["memory_kills_in_window"]["value"] == 0
    assert 0 < got["queries_in_flight_mean"]["value"] <= 3.0
    assert got["queue_wait_p95_ms"]["value"] >= 0.0
    assert got["host_staging_mb_per_q"]["value"] == 0.0
    for name in ("query_hbm_roofline", "device_time_attributed_share",
                 "join_device_ms_per_q", "idle_unattributed_share"):
        assert name not in got
