"""`semi_build_set_share` (PR 46): what it computes from the two counters
the join router books a semi, anti or mark join's build lanes to, and that
it returns nothing, and does not raise, where there is nothing to read — a
program without the counters (the parent), no executed query, no such
join."""

import json
import os

import pytest

import rehearsal
from reference import load_by_path

read = load_by_path("layer_metrics", "semi_build_set_share").read


def request(shape="q4", **stats):
    return {"shape": shape, "params": {}, "t_send": 0.0, "t_done": 1.0,
            "info": {"stats": {"result_cache_hits": 0, **stats}}}


def test_the_share_is_set_lanes_over_all_the_semi_builds_lanes():
    q4 = request(semi_build_lanes_set=60_030_976, semi_build_lanes_sorted=0)
    q18 = request("q18", semi_build_lanes_set=0,
                  semi_build_lanes_sorted=1024)
    assert read({"requests": [q4]}) == 100.0
    assert read({"requests": [q18]}) == 0.0
    assert read({"requests": [q18, q4, q18, q4]}) == pytest.approx(
        100 * 60_030_976 / 60_032_000)
    assert read({"requests": [q18, q4, q18]}) == pytest.approx(
        100 * 60_030_976 / (60_030_976 + 2 * 1024))


def test_nothing_to_read_is_none():
    # the parent: no such counters
    assert read({"requests": [request(semi_join_build_rows=9)]}) is None
    assert read({"requests": [request(semi_build_lanes_set=9)]}) is None
    assert read({"requests": [{"info": None}]}) is None
    assert read({"requests": []}) is None
    # queries with no semi, anti or mark join: no lanes
    assert read({"requests": [request(
        "q6", semi_build_lanes_set=0, semi_build_lanes_sorted=0)]}) is None
    # a result-cache hit ran nothing
    hit = request(semi_build_lanes_set=5, semi_build_lanes_sorted=5)
    hit["info"]["stats"]["result_cache_hits"] = 1
    assert read({"requests": [hit]}) is None


def test_the_metric_is_declared_for_the_cell_with_semi_joins():
    with open(os.path.join(rehearsal.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["per_layer"][-1] == {
        "name": "semi_build_set_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "ops kernels",
        "moves": "throughput_qps", "workloads": ["sf10-power-q18-q4"]}
    assert "sf10-power-q18-q4" in {w["name"] for w in bench["workloads"]}


def test_semijoin_ms_reads_the_set_builds_programs_under_their_names():
    """`semijoin_device_ms_per_q` matches programs and scopes by prefix
    (`join__semi`, `join__mark`): the set build's two programs
    (`join__semijoin_stats`, `join__semijoin_set_table`) are read beside
    the probe with no edit to the metric; an inner join's are not."""
    semijoin = load_by_path("layer_metrics", "semijoin_device_ms_per_q")
    owners = {"join__semijoin/join__semi_probe": 0.2,
              "join__semijoin/join__compact_shift": 0.1,
              "join__markjoin/join__mark_probe": 0.05,
              "join__semijoin_stats/join__semi_build": 0.02,
              "join__semijoin_set_table/join__semi_build": 0.08,
              "join__semijoin_prep/join__radix_pass": 0.3,
              "join__uprobe/join__probe_lookup": 4.0}
    def traced(by_owner):
        # the context `trace_programs.table` keeps once it has reduced
        # the xplane: two queries wholly inside a 10 s slice
        requests = [dict(request(shape), t_send=0.0, t_done=10.0)
                    for shape in ("q18", "q4")]
        return {"requests": requests, "slice": (0.0, 10.0), "chips": [0],
                "trace": {"busy_s": 1.0},
                "_trace_programs": {"by_family": {"join": 4.75},
                                    "by_owner": by_owner}}
    assert semijoin.read(traced(owners)) == pytest.approx(1e3 * 0.75 / 2)
    inner = {"join__uprobe/join__probe_lookup": 4.0}
    assert semijoin.read(traced(inner)) is None
