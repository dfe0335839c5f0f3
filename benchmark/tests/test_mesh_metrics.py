"""The four per-layer metrics `sf30-mesh4-power` brought (PR 28), on made-up
readings: what each computes, and that each returns nothing — and does not
raise — where the program or the run has nothing for it to read (the parent
commit, a one-chip cell, an untraced or CPU run)."""

import pytest

from reference import load_by_path


def metric(name):
    return load_by_path("layer_metrics", name)


def request(stats):
    return {"info": {"stats": {"result_cache_hits": 0, **stats}}}


def test_rounds_per_program_is_dispatches_over_programs():
    read = metric("mesh_rounds_per_program").read
    steady = [request({"mesh_programs": 1, "mesh_program_rounds": 1}),
              request({"mesh_programs": 1, "mesh_program_rounds": 1})]
    assert read({"requests": steady}) == 1.0
    climbed = steady + [request({"mesh_programs": 1,
                                 "mesh_program_rounds": 3})]
    assert read({"requests": climbed}) == pytest.approx(5 / 3)
    # a local runner's queries, and a program without the counters
    assert read({"requests": [request({"mesh_programs": 0,
                                       "mesh_program_rounds": 0})]}) is None
    assert read({"requests": [request({})]}) is None
    assert read({"requests": []}) is None


def test_busy_skew_is_the_busiest_chip_over_the_mean():
    read = metric("chip_busy_skew").read
    assert read({"trace": {"busy_s_per_chip": [9.75, 6.21, 6.22, 6.22]}}) \
        == pytest.approx(9.75 / 7.10, rel=1e-3)
    assert read({"trace": {"busy_s_per_chip": [5.0, 5.0, 5.0, 5.0]}}) == 1.0
    for nothing in ({"trace": None}, {}, {"trace": {"planes": {}}},
                    {"trace": {"busy_s_per_chip": [4.2]}},
                    {"trace": {"busy_s_per_chip": [0.0, 0.0]}}):
        assert read(nothing) is None


def event(name, start, duration):
    return ({"name": name}, start, duration)


def test_collective_share_counts_the_wire_only_where_nothing_hides_it():
    m = metric("collective_device_share")
    assert m.opcode("%all-gather-start.3 = (u32[4]{0}, u32[16]{0}) "
                    "all-gather-start(u32[4]{0} %x), dimensions={0}") \
        == "all-gather-start"
    assert m.opcode("%all-to-all.12") == "all-to-all"
    assert m.opcode("%fusion.3 = s32[8]{0} fusion(s32[8]{0} %p), "
                    "kind=kLoop") == "fusion"
    events = [event("%fusion.1 = s32[] fusion()", 0, 10),
              event("%all-gather-start.1 = () all-gather-start()", 10, 1),
              event("%fusion.2 = s32[] fusion()", 12, 3),   # hides 3 of it
              event("%all-gather-done.1 = () all-gather-done()", 20, 1),
              event("%all-to-all.2 = s32[] all-to-all()", 30, 5),
              event("%all-reduce.9 = s64[] all-reduce()", 200, 5)]  # outside
    wire, other = m.split(events, 0, 100)
    assert sorted(wire) == [(10, 11), (10, 21), (30, 35)]
    assert sorted(other) == [(0, 10), (12, 15)]
    assert m.exposed(wire, other) == (21 - 10 - 3) + 5
    # an untraced run, and a traced one whose xplane has gone
    assert m.read({"trace": None, "slice": None, "chips": [0]}) is None


def test_exchange_family_reads_nothing_without_a_table():
    read = metric("exchange_device_ms_per_q").read
    assert read({"requests": [], "_trace_programs": None}) is None
    table = {"by_family": {"exchange": 6.0, "join": 2.0}}
    ctx = {"_trace_programs": table, "slice": (0.0, 30.0), "requests": [
        dict(request({}), t_send=0.0, t_done=20.0),
        dict(request({}), t_send=20.0, t_done=40.0)]}
    assert read(ctx) == pytest.approx(1e3 * 6.0 / 1.5)
