"""The served mesh runner (PR 28): a `DistributedQueryRunner` over four of
conftest's eight host devices behind `TrinoServer`, as the benchmark's
`sf30-mesh4-power` cell builds it, at `tiny`.

  answers     q1 and q3 under PARTITIONED, on the benchmark's own seeded
              traffic, equal `LocalQueryRunner`'s rows and the NumPy
              reference's (`benchmark/reference.py`), wholly on the mesh
  shards      after the table warm-up chip i holds ITS rows of every
              warmed column, made on chip i; mesh scans move nothing
  parameters  numeric, date and interval literals are operands of the
              mesh program: another DELTA or DATE compiles nothing, a
              string keys the program of its own fragment and no other
  the ladder  converged capacities are remembered by program shape
"""

import json
import os
import sys

import jax
import numpy as np
import pytest

from trino_tpu.exec import LocalQueryRunner, mesh_exec
from trino_tpu.exec.distributed import DistributedQueryRunner
from trino_tpu.exec.memory import NODE_POOL
from trino_tpu.server import TrinoServer

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
for _path in (BENCH, os.path.join(BENCH, "queries")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import loadgen          # noqa: E402
import q1               # noqa: E402
import q3               # noqa: E402
import reference        # noqa: E402
import traffic_gen      # noqa: E402

N = 4
SEED = 2147483659       # larger than 32 signed bits hold, as the driver's
with open(os.path.join(BENCH, "configs", "tpch-sf30-4chip.json")) as _f:
    COLUMNS = json.load(_f)["columns"]
TRAFFIC = traffic_gen.load_traffic("power-q1-q3")
# the first three q1 -> q3 cycles the load generator would send
REQUESTS = traffic_gen.make_plan(TRAFFIC, SEED, 2)["clients"][0][:6]


def _devices():
    if len(jax.devices()) < N:
        pytest.skip(f"needs {N} devices")
    return jax.devices()[:N]


def _mesh_runner():
    runner = DistributedQueryRunner.tpch("tiny", devices=_devices())
    runner.session.set("join_distribution_type", "PARTITIONED")
    return runner


def _manifest():
    return {"tables": [{"table": f"tpch.tiny.{t}", "columns": names}
                       for t, names in COLUMNS.items()]}


class _Served:
    def __init__(self, runner, **kwargs):
        self.runner = runner
        self.server = TrinoServer(runner, **kwargs).start()
        self.conn = loadgen.Conn("127.0.0.1", self.server.port, "test")

    def run(self, shape, params):
        sql = {"q1": q1, "q3": q3}[shape].SQL.format(**params)
        got = self.conn.statement(sql, loadgen.session_header(
            TRAFFIC["session"]))
        assert got["error"] is None, got
        return got["rows"], self.conn.get(f"/v1/query/{got['qid']}")["stats"]

    def stop(self):
        self.conn.close()
        self.server.stop()


@pytest.fixture(scope="module")
def mesh():
    served = _Served(_mesh_runner(), warmup_manifest=_manifest())
    yield served
    served.stop()


@pytest.fixture(scope="module")
def local():
    served = _Served(LocalQueryRunner.tpch("tiny"))
    yield served
    served.stop()


@pytest.fixture(scope="module")
def wanted():
    """The plain reference's rows for each request, worked out once."""
    keys = [(shape, params) for shape, params in REQUESTS]
    return reference.compute(0.01, keys, 2)


# ------------------------------------------------------------------ answers

@pytest.mark.parametrize("i", range(len(REQUESTS)),
                         ids=[f"{s}-{i}" for i, (s, _) in enumerate(REQUESTS)])
def test_served_mesh_answers_as_local_and_reference(mesh, local, wanted, i):
    shape, params = REQUESTS[i]
    rows, stats = mesh.run(shape, params)
    assert rows and rows == local.run(shape, params)[0]
    assert reference.compare(rows, wanted[i]) == ""
    assert stats["mesh_devices"] == N and stats["exchanges_staged"] == 0
    assert stats["exchanges_fused"] >= 1
    # resident shards: the scans generated, copied and staged nothing
    assert stats["mesh_scan_moved_bytes"] == 0
    assert stats["scan_host_staging_bytes"] == 0
    assert stats["scan_staging_bytes"] == 0
    assert stats["table_cache_hits"] == {"q1": 1, "q3": 3}[shape]
    assert stats["mesh_program_rounds"] == stats["mesh_programs"] \
        == {"q1": 1, "q3": 2}[shape]
    assert "mesh_stage" in {name for name, _, _ in stats["spans"]}


@pytest.mark.parametrize("where", ["mesh", "local"])
def test_q1_reduces_its_twelve_slots_under_masks_on_both_runners(
        mesh, local, where):
    """PR 29: q1's direct GROUP BY takes the masked form (12 slots x 15
    states), counted once per dispatch of the program that holds it — the
    one mesh program, or each of the local runner's chain dispatches — and
    nothing of q1 scatters over the page; q3's GROUP BY is sorted."""
    served = {"mesh": mesh, "local": local}[where]
    stats = {shape: served.run(shape, params)[1]
             for shape, params in REQUESTS[:2]}
    assert stats["q1"]["direct_reduces_scattered"] == 0
    assert stats["q1"]["direct_reduces_masked"] == (
        stats["q1"]["mesh_program_rounds"] if where == "mesh"
        else stats["q1"]["compactions_deferred"]) >= 1
    assert stats["q3"]["direct_reduces_masked"] == 0
    assert stats["q3"]["direct_reduces_scattered"] == 0


def test_the_window_draws_new_parameters_and_compiles_nothing(mesh):
    """DELTA and DATE differ from request to request, SEGMENT is the
    run's: after one cycle every request dispatches warm executables."""
    assert len({json.dumps(p, sort_keys=True) for _, p in REQUESTS}) == 6
    assert len({p["segment"] for s, p in REQUESTS if s == "q3"}) == 1
    for shape, params in REQUESTS[:2]:
        mesh.run(shape, params)
    for shape, params in REQUESTS[2:]:
        stats = mesh.run(shape, params)[1]
        assert stats["jit_misses"] == 0, (shape, params)
        assert stats["mesh_params"] > 0


# ------------------------------------------------------------------- shards

@pytest.mark.parametrize("table", sorted(COLUMNS))
def test_warmed_shards_live_on_their_own_chips(mesh, table):
    runner = mesh.runner
    entry = runner._table_cache.lookup_sharded(
        ("tpch", "tiny", table), COLUMNS[table], N, count=False)
    assert entry is not None and sorted(entry.columns) \
        == sorted(COLUMNS[table])
    devices = [runner.mesh.device_of(i) for i in range(N)]
    for column in entry.columns.values():
        blocks = sorted(column.values.addressable_shards,
                        key=lambda s: s.index[0].start)
        assert [b.data.devices() for b in blocks] \
            == [{d} for d in devices]
        assert all(b.data.shape == (1, entry.capacity) for b in blocks)
    assert entry.shard_bytes * N == sum(
        c.nbytes for c in entry.columns.values())
    # admission is per chip, and even (chip 0 also holds what the
    # process's local runners promoted)
    held = [NODE_POOL.device_cache_reserved.get(i, 0) for i in range(N)]
    assert held[0] >= held[1] == held[2] == held[3] >= entry.shard_bytes


@pytest.mark.parametrize("table", sorted(COLUMNS))
def test_shards_concatenated_are_the_local_runners_columns(mesh, table):
    runner = mesh.runner
    names = COLUMNS[table]
    entry = runner._table_cache.lookup_sharded(
        ("tpch", "tiny", table), names, N, count=False)
    conn = runner.catalogs.get("tpch")
    handle = conn.metadata.get_table_handle(
        runner.metadata.resolve_table_name(
            ("tpch", "tiny", table), runner.session).schema_table)
    by_name = {c.name: c for c in conn.metadata.get_column_handles(handle)}
    (split,) = conn.split_manager.get_splits(handle, target_splits=1)
    (page,) = conn.page_source.pages(
        split, [by_name[n] for n in names], 1 << 17)
    rows = [int(r) for r in jax.device_get(entry.num_rows)]
    assert sum(rows) == int(page.num_rows) == entry.rows
    for name, whole in zip(names, page.columns):
        mine = entry.columns[name]
        assert mine.dictionary is whole.dictionary and mine.valid is None
        blocks = np.asarray(mine.values)
        got = np.concatenate([blocks[i, :rows[i]] for i in range(N)])
        want = np.asarray(whole.values)[:entry.rows]
        assert got.dtype == want.dtype and got.tobytes() == want.tobytes()


def test_each_shard_is_made_on_its_own_chip(monkeypatch):
    """Table warm-up pulls shard i's splits with chip i as the default
    device, so no chip ever holds another's rows, and the connector's
    device-column LRU (the default device's) keeps none of them."""
    from trino_tpu.connector import tpch
    from trino_tpu.serve.warmup import preload_table
    runner = _mesh_runner()
    runner.session.set("table_cache_enabled", True)
    source = runner.catalogs.get("tpch").page_source
    seen = []
    pages = source.pages

    def spy(split, columns, capacity):
        seen.append((split.part, jax.config.jax_default_device))
        return pages(split, columns, capacity)
    monkeypatch.setattr(source, "pages", spy)
    cached = len(tpch._DEVICE_COL_CACHE)
    report = preload_table(runner, "tpch.tiny.orders", COLUMNS["orders"])
    assert report["resident"] and report["shards"] == N \
        and report["rows"] == 15000
    assert seen == [(i, runner.mesh.device_of(i)) for i in range(N)]
    assert len(tpch._DEVICE_COL_CACHE) == cached
    # the full-length tier (table_cache_max_bytes' side) holds nothing
    assert runner._table_cache.resident_bytes == 0
    assert runner._table_cache.sharded_bytes > 0
    runner._table_cache.clear()
    assert runner._table_cache.sharded_bytes == 0


def test_a_mesh_scan_without_warm_up_counts_what_it_made():
    runner = _mesh_runner()
    runner.execute(q1.SQL.format(delta=90))
    stats = runner.last_query_stats
    assert stats["mesh_scan_moved_bytes"] == stats["scan_staging_bytes"] > 0
    assert stats["scan_host_staging_bytes"] == 0    # made on the device


# --------------------------------------------------------------- parameters

# two market segments this module's window does not send: programs with
# these strings in their keys are this test's alone
_OTHERS = [s for s in q3.DOMAIN["segment"]
           if s != next(p["segment"] for n, p in REQUESTS if n == "q3")]


@pytest.mark.parametrize("shape, first, second, warm", [
    ("q1", {"delta": 90}, {"delta": 64}, True),
    ("q3", {"segment": _OTHERS[0], "date": "1995-03-15"},
     {"segment": _OTHERS[0], "date": "1995-03-04"}, True),
    ("q3", {"segment": _OTHERS[0], "date": "1995-03-15"},
     {"segment": _OTHERS[1], "date": "1995-03-15"}, False)],
    ids=["q1-delta", "q3-date", "q3-segment"])
def test_parameters_are_operands_of_the_mesh_program(shape, first, second,
                                                     warm):
    sql = {"q1": q1, "q3": q3}[shape].SQL
    runner, oracle = _mesh_runner(), LocalQueryRunner.tpch("tiny")
    oracle.session.set("join_distribution_type", "PARTITIONED")
    answers, truths = [], []
    for params in (first, second):
        answers.append(runner.execute(sql.format(**params)).rows)
        truths.append(oracle.execute(sql.format(**params)).rows)
    stats = runner.last_query_stats
    # they differ as the truth does (at `tiny` no lineitem ships in q1's
    # last 120 days, so every DELTA reads the whole table)
    assert answers == truths
    assert (answers[0] != answers[1]) == (shape == "q3")
    assert stats["mesh_params"] > 0
    if warm:    # the executables the first one compiled
        assert stats["jit_misses"] == 0 and stats["jit_compiles"] == 0
        assert stats["jit_param_hits"] >= 1
    else:       # a string literal stays in its program's key (M4): the
        #         customer fragment's own, a filter, and nothing else compiles
        assert stats["jit_misses"] == 1 and stats["mesh_programs"] == 2


def test_a_string_keys_the_program_of_its_own_fragment_and_no_other(
        monkeypatch):
    """q3's SEGMENT is drawn once per run and stays in a program's key: the
    fragment that filters customer by it runs as a program of its own, and
    hands its page to the one with the joins, the GROUP BY and the TopN —
    whose key, and so whose executable (minutes of compile at SF30), is the
    same for every SEGMENT."""
    keys = []
    run_program = mesh_exec._run_program

    def spy(runner, top_fn, staged, struct_key, ladder, params):
        keys.append(struct_key)
        return run_program(runner, top_fn, staged, struct_key, ladder,
                           params)
    monkeypatch.setattr(mesh_exec, "_run_program", spy)
    runner = _mesh_runner()
    for segment in _OTHERS[:2]:
        runner.execute(q3.SQL.format(segment=segment, date="1995-03-15"))
        assert runner.last_query_stats["mesh_programs"] == 2
        assert runner.last_query_stats["exchanges_staged"] == 0
    (small_a, big_a), (small_b, big_b) = keys[:2], keys[2:]
    assert big_a == big_b and small_a != small_b
    assert _OTHERS[0] in repr(small_a) and _OTHERS[1] in repr(small_b)
    assert not any(s in repr(big_a) for s in q3.DOMAIN["segment"])


def test_execute_values_are_operands_too():
    runner = _mesh_runner()
    runner.execute("PREPARE p FROM SELECT count(*), sum(l_quantity) "
                   "FROM lineitem WHERE l_quantity < ? AND l_shipdate > ?")
    a = runner.execute("EXECUTE p USING 24, DATE '1995-03-15'").rows
    b = runner.execute("EXECUTE p USING 11, DATE '1996-01-01'").rows
    stats = runner.last_query_stats
    assert a != b and stats["jit_misses"] == 0 and stats["mesh_params"] == 2
    oracle = LocalQueryRunner.tpch("tiny")
    assert b == oracle.execute(
        "SELECT count(*), sum(l_quantity) FROM lineitem WHERE "
        "l_quantity < 11 AND l_shipdate > DATE '1996-01-01'").rows


# --------------------------------------------------------------- the ladder

SKEWED = ("SELECT l_linenumber, count(DISTINCT l_orderkey) FROM lineitem "
          "GROUP BY l_linenumber")


def test_converged_capacities_are_remembered_by_shape():
    """Seven keys over four chips overflow the first bucket guess: the
    first query climbs, the next one of the shape starts where it ended."""
    runner = _mesh_runner()
    runner.execute("SET SESSION page_capacity = 16384")
    first = runner.execute(SKEWED)
    climbed = runner.last_query_stats
    assert climbed["mesh_program_rounds"] > climbed["mesh_programs"] >= 1
    again = runner.execute(SKEWED)
    steady = runner.last_query_stats
    assert steady["mesh_program_rounds"] == steady["mesh_programs"]
    assert steady["jit_misses"] == 0
    oracle = LocalQueryRunner.tpch("tiny").execute(SKEWED)
    assert sorted(first.rows) == sorted(again.rows) == sorted(oracle.rows)
    # kept by structure key, never shrunk
    kept = [ladder for key, ladder in mesh_exec._LADDERS.items() if ladder]
    assert kept and all(v >= 1024 for d in kept for v in d.values())


# ------------------------------------------------------------------- chunks

@pytest.mark.parametrize("sql", [
    q1.SQL.format(delta=75),
    "SELECT sum(l_extendedprice * l_discount), count(*) FROM lineitem "
    "WHERE l_quantity < 24",
    "SELECT l_orderkey, count(*) FROM lineitem WHERE l_quantity < 5 "
    "GROUP BY l_orderkey ORDER BY 2 DESC, 1 LIMIT 5"],
    ids=["direct", "global", "sorted"])
def test_small_state_partial_aggregates_run_a_chunk_at_a_time(monkeypatch,
                                                              sql):
    """An SF30 shard is 46 M lanes and the direct path's stacked
    scatter-add would ask the chip for 23.6 GB: inside a mesh program the
    chain under a small-state partial aggregate takes `_CHUNK_LANES` at a
    time. Here with chunks of 4 096 lanes; a sorted GROUP BY is not cut."""
    monkeypatch.setattr(mesh_exec, "_CHUNK_LANES", 4096)
    runner = _mesh_runner()
    got = runner.execute(sql)
    assert runner.last_query_stats["exchanges_staged"] == 0
    assert got.rows == LocalQueryRunner.tpch("tiny").execute(sql).rows
