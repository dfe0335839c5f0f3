"""trace_reduce.py on the small xplane recorded on the v5e by
record_sample_trace.py and kept beside it: three "requests" (a, b, a) of
four sorts each with 2 ms sleeps inside and 20 ms sleeps between, inside
the slice annotations."""

import json
import os

import pytest

import trace_reduce

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
XPLANE = os.path.join(BENCH, "trace_sample.xplane.pb")


@pytest.fixture(scope="module")
def sample():
    with open(os.path.join(BENCH, "trace_sample.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(sample):
    return trace_reduce.reduce_xplane(XPLANE, sample["requests"],
                                      sample["t_begin"])


def test_same_file_same_numbers(sample, reduced):
    """What a later PR computes from this file is what PR 24 computed."""
    for key in ("busy_s", "window_s", "devices_traced", "device_ops",
                "idle_gaps", "idle_in_request_s"):
        assert reduced[key] == sample["reduced"][key], key


def test_only_the_cells_chips_are_read(sample, reduced):
    """A chip of the cell's that ran nothing is an idle chip, not a
    missing one: the mean falls, the per-chip seconds say which; and a
    cell on another chip does not read chip 0's plane."""
    four = trace_reduce.reduce_xplane(XPLANE, sample["requests"],
                                      sample["t_begin"], chips=(0, 1, 2, 3))
    assert four["busy_s_per_chip"] == [reduced["busy_s"], 0.0, 0.0, 0.0]
    assert four["busy_s"] == pytest.approx(reduced["busy_s"] / 4)
    assert dict(four["device_ops"])["%sort.6 sort"] == pytest.approx(
        dict(reduced["device_ops"])["%sort.6 sort"] / 4)
    assert four["idle_gaps"] == reduced["idle_gaps"]
    assert reduced["busy_s_per_chip"] == [reduced["busy_s"]]
    assert "busy_s" not in trace_reduce.reduce_xplane(
        XPLANE, sample["requests"], sample["t_begin"], chips=(1,))


def test_busy_is_the_union_of_the_device_ops(reduced):
    assert reduced["planes"]["/device:TPU:0"]["XLA Ops"] == 48
    ops = dict(reduced["device_ops"])
    # 12 sorts of 2^20 int32 at ~1.1 ms do nearly all the work; ops on
    # one device do not overlap, so their sum is the busy time
    assert ops["%sort.6 sort"] == pytest.approx(0.01335, rel=0.01)
    assert sum(ops.values()) == pytest.approx(reduced["busy_s"], rel=1e-6)
    assert 0.13 < reduced["window_s"] < 0.15
    assert reduced["busy_s"] < 0.1 * reduced["window_s"]


def test_idle_gaps_are_laid_on_the_requests(sample, reduced):
    """Idle time sums to slice - busy; the four 20 ms sleeps fall between
    requests, the 2 ms sleeps inside them, by the shapes in flight."""
    gaps = dict(reduced["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert set(gaps) == {"between_requests", "in_request:a",
                         "in_request:b"}
    assert 0.08 < gaps["between_requests"] < 0.1
    assert gaps["in_request:a"] == pytest.approx(
        2 * gaps["in_request:b"], rel=0.1)
    in_flight = sum(r["t_done"] - r["t_send"] for r in sample["requests"])
    assert gaps["in_request:a"] + gaps["in_request:b"] < in_flight


def test_the_anchor_moves_the_requests_with_it(sample):
    """The requests reach the trace's clock through the begin
    annotation's time.monotonic(): shift that by a second and every gap
    falls between requests."""
    moved = trace_reduce.reduce_xplane(XPLANE, sample["requests"],
                                       sample["t_begin"] + 1.0)
    assert [name for name, _ in moved["idle_gaps"]] == ["between_requests"]


def test_helpers():
    assert trace_reduce._union([(0, 4), (2, 6), (10, 11)]) == 7
    assert trace_reduce._gaps([(2, 4), (3, 5), (8, 9)], 0, 10) \
        == [(0, 2), (5, 8), (9, 10)]
    assert trace_reduce.short_name(
        "%fusion.3 = (s64[8]{0}, pred[]) fusion(s32[8]{0} %p), "
        "kind=kLoop, calls=%fused") == "%fusion.3 fusion kLoop"
    assert trace_reduce.short_name("plain-name") == "plain-name"
