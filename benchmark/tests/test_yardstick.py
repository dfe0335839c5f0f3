"""The pieces of the yardstick that need no server: the traffic
generator, the column generator against the engine's own, the shapes'
byte counts against the engine's page dtypes, the comparison."""

import collections
import json
import os

import numpy as np
import pytest

import reference
import rehearsal
import tpch_columns as C
import traffic_gen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAFFIC = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")))
CONFIGS = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "configs")))


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", TRAFFIC)
def test_same_seed_same_plan_and_large_seeds_work(name):
    traffic = traffic_gen.load_traffic(name)
    seed = 2 ** 31 + 12345
    a = traffic_gen.make_plan(traffic, seed, 51)
    b = traffic_gen.make_plan(traffic, seed, 51)
    c = traffic_gen.make_plan(traffic, seed + 1, 51)
    assert json.dumps(a) == json.dumps(b)
    assert json.dumps(a["clients"]) != json.dumps(c["clients"])
    assert a["n_clients"] == traffic["clients"]
    assert len(a["clients"]) == (1 if traffic["queue"] == "shared"
                                 else traffic["clients"])
    # warm-up: one statement per shape, cache off, never a window request
    warm = [s for s in a["setup"] if s["phase"] == "warmup"]
    assert [s["shape"] for s in warm] == [e["shape"]
                                          for e in traffic["shapes"]]
    assert all(s["session"]["result_cache_enabled"] == "false"
               for s in warm)
    assert sum(s["phase"] == "prefill" for s in a["setup"]) \
        == traffic["prefill_ranks"] * len(traffic["shapes"])


def test_every_seed_gets_the_same_work_in_another_order():
    """Weighted mix: each block of 20 holds 12 q6, 5 q1 and 3 q3 whatever
    the seed, and the hot ranks carry the Zipf mass in every window."""
    traffic = traffic_gen.load_traffic("dashboard-zipf-8c")
    shares = []
    for seed in (1, 2, 3000000019):
        plan = traffic_gen.make_plan(traffic, seed, 51)
        hot = {name: {json.dumps(s["params"], sort_keys=True)
                      for s in plan["setup"]
                      if s["phase"] == "prefill" and s["shape"] == name}
               for name in ("q6", "q1", "q3")}
        for reqs in plan["clients"]:
            for at in range(0, 200, 20):
                block = collections.Counter(s for s, _ in reqs[at:at + 20])
                assert block == {"q6": 12, "q1": 5, "q3": 3}
        first = [r for reqs in plan["clients"] for r in reqs[:40]]
        shares.append(sum(json.dumps(p, sort_keys=True) in hot[s]
                          for s, p in first) / len(first))
        # one SEGMENT per run, in every q3 of the plan
        assert len({p["segment"] for reqs in plan["clients"]
                    for s, p in reqs[:200] if s == "q3"}) == 1
    # 8 of 80 / 61 / 31 ranks under Zipf(1) hold 55-67 % of the mass
    assert all(0.5 < s < 0.72 for s in shares), shares
    assert max(shares) - min(shares) < 0.06, shares


@pytest.mark.parametrize("name", CONFIGS + ["tpch-sf30-4chip, made here"])
def test_configuration_states_its_data(name):
    if name in CONFIGS:
        config = _config(name)
    else:   # a deployment above SF10, as the PR that adds it would state it
        config = rehearsal.at_scale(_config("tpch-sf10-1chip"),
                                    "tpch-sf30-4chip", "sf30",
                                    runner="mesh", chips=4)
        assert config["rows"] == {"lineitem": 180000093,
                                  "orders": 45000000, "customer": 4500000}
    sf = config["scale_factor"]
    assert C.SCALE_FACTORS[config["schema"]] == sf
    assert C.fingerprint(sf) == config["data_fingerprint"]
    if sf <= 1:     # counting SF10's lineitem takes a while
        assert C.row_counts(sf) == config["rows"]
    assert len(config["source"]) <= 200
    assert sorted(c for cols in config["columns"].values() for c in cols) \
        == sorted(config["column_bytes"])


def test_the_copied_generator_equals_the_engines():
    """tpch_columns.py is a copy of connector/tpch_gen.py's streams: a
    range of orders in the middle of SF1, every column equal."""
    from trino_tpu.connector import tpch_gen as G
    sf, a, b = 1.0, 700_000, 703_000
    chunk = C.orders_chunk(sf, a, b)
    _, starts = G._line_index(sf)
    for name, got in chunk.items():
        table = "orders" if name.startswith("o_") else "lineitem"
        lo, hi = (a, b) if table == "orders" else (int(starts[a]),
                                                   int(starts[b]))
        make = G.codes_chunk if G.string_kind(table, name) \
            else G.numeric_chunk
        assert np.array_equal(got, make(table, sf, name, lo, hi)), name
    customer = C.customer(sf)
    n = C.customer_count(sf)
    assert np.array_equal(customer["c_mktsegment"], G.codes_chunk(
        "customer", sf, "c_mktsegment", 0, n))
    assert list(G.pool_values("customer", "c_mktsegment", sf)) \
        == list(C.SEGMENTS)
    assert list(G.pool_values("lineitem", "l_returnflag", sf)) \
        == list(C.RETURNFLAGS)
    assert list(G.pool_values("lineitem", "l_linestatus", sf)) \
        == list(C.LINESTATUSES)
    assert C.row_counts(sf)["lineitem"] == G.row_count("lineitem", sf)


def test_needed_bytes_uses_the_widths_the_engine_stores():
    """The widths in the configuration files are what the engine's own
    pages hold at `tiny`, read here and not typed by hand."""
    from trino_tpu.connector import tpch
    config = _config("tpch-sf10-1chip")
    for table, names in config["columns"].items():
        types = dict(tpch.TABLES[table][0])
        for name in names:
            column = tpch._staged_column(table, 0.01, name, types[name],
                                         0, 128, 128)
            assert column.values.dtype.itemsize \
                == config["column_bytes"][name], name
    rows, widths = config["rows"], config["column_bytes"]
    q6 = reference.load_by_path("queries", "q6")
    assert q6.needed_bytes(rows, widths) == rows["lineitem"] * (4 + 8 * 3)
    q3 = reference.load_by_path("queries", "q3")
    assert q3.needed_bytes(rows, widths) == (
        rows["customer"] * 12 + rows["orders"] * 24
        + rows["lineitem"] * 28)


def test_a_roofline_share_above_100_raises():
    metric = reference.load_by_path("layer_metrics", "query_hbm_roofline")
    config = _config("tpch-sf10-1chip")
    request = {"shape": "q6", "t_send": 0.0, "t_done": 4.0,
               "info": {"stats": {"result_cache_hits": 0}}}
    ctx = {"requests": [request], "slice": (0.0, 4.0), "config": config,
           "peaks": {"hbm_bytes_per_s": 819e9}, "chips": [0],
           "shapes": {"q6": reference.load_by_path("queries", "q6")},
           "trace": {"busy_s": 3.5, "window_s": 4.0}}
    share = metric.read(ctx)
    needed = config["rows"]["lineitem"] * 28
    assert share == pytest.approx(100 * needed / 819e9 / 3.5)
    # four chips read four times as fast: the same busy seconds are a
    # quarter of the share
    assert metric.read({**ctx, "chips": [0, 1, 2, 3]}) \
        == pytest.approx(share / 4)
    ctx["trace"] = {"busy_s": 1e-3, "window_s": 4.0}
    with pytest.raises(ValueError, match="above 100"):
        metric.read(ctx)
    ctx["trace"] = None
    assert metric.read(ctx) is None


def test_compare_is_exact():
    want = [["A", "10.00", 3]]
    assert reference.compare([["A", "10.00", 3]], want) == ""
    assert reference.compare([[1.0 + 1e-12]], [[1.0]]) == ""
    for got in ([["A", "10.01", 3]], [["A", "10.00", 3.0]],
                [["A", "10.00", 3], ["B", "1.00", 1]], [["A", "10.00"]]):
        assert reference.compare(got, want) != ""
    assert reference.compare([[1.0 + 1e-6]], [[1.0]]) != ""


def test_reference_equals_chip_smokes_at_tiny():
    """The parallel partial/merge reference against the whole-table NumPy
    references of chip_smoke.py, which PR 23 proved on the chip."""
    import chip_smoke
    cols = chip_smoke.host_columns("tiny")
    keys = [("q6", {"date": "1994-01-01", "disc": "0.06", "qty": 24}),
            ("q1", {"delta": 90}),
            ("q3", {"segment": "BUILDING", "date": "1995-03-15"})]
    got = reference.compute(0.01, keys, 2)
    want = [chip_smoke.ref_q6(cols, "1994-01-01", "0.06", 24),
            chip_smoke.ref_q1(cols), chip_smoke.ref_q3(cols)]
    for g, w in zip(got, want):
        assert reference.compare(g, w) == ""
