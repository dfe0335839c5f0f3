"""`groupby_in_order_share` (PR 45): what it computes from the two counters
the sorted GROUP BY books its lanes to, and that it returns nothing, and
does not raise, where there is nothing to read — a program without the
counters (the parent), no executed query, no sorted GROUP BY."""

import json
import os

import pytest

import rehearsal
from reference import load_by_path

read = load_by_path("layer_metrics", "groupby_in_order_share").read


def request(shape="q18", **stats):
    return {"shape": shape, "params": {}, "t_send": 0.0, "t_done": 1.0,
            "info": {"stats": {"result_cache_hits": 0, **stats}}}


def test_the_share_is_lanes_in_order_over_all_the_sorted_group_bys_lanes():
    q18 = request(group_by_lanes_in_order=77_594_624,
                  group_by_lanes_sorted=1024)
    q4 = request("q4", group_by_lanes_in_order=0, group_by_lanes_sorted=0)
    assert read({"requests": [q18, q4, q18, q4]}) == pytest.approx(
        100 * 77_594_624 / 77_595_648)
    q9 = request("q9", group_by_lanes_in_order=0, group_by_lanes_sorted=4096)
    assert read({"requests": [q9]}) == 0.0
    assert read({"requests": [q18, q9]}) == pytest.approx(
        100 * 77_594_624 / (77_595_648 + 4096))


def test_nothing_to_read_is_none():
    # the parent: no such counters
    assert read({"requests": [request(sorted_reduce_lanes=9)]}) is None
    assert read({"requests": [request(group_by_lanes_sorted=9)]}) is None
    assert read({"requests": [{"info": None}]}) is None
    assert read({"requests": []}) is None
    # queries with no sorted GROUP BY: no lanes
    assert read({"requests": [request(
        "q6", group_by_lanes_in_order=0, group_by_lanes_sorted=0)]}) is None
    # a result-cache hit ran nothing
    hit = request(group_by_lanes_in_order=5, group_by_lanes_sorted=5)
    hit["info"]["stats"]["result_cache_hits"] = 1
    assert read({"requests": [hit]}) is None


def test_the_metric_is_declared_for_the_cells_with_a_sorted_group_by():
    with open(os.path.join(rehearsal.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "groupby_in_order_share")
    assert entry == {
        "name": "groupby_in_order_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "ops kernels",
        "moves": "throughput_qps",
        # in the cells' own order: the older cells' twins take a metric
        # whose list STARTS with their cell for one their PR brought
        "workloads": ["sf10-join", "sf10-power-q18-q4", "sf10-power-q9",
                      "sf10-power-q13"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry["workloads"]) <= cells
