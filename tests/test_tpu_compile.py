"""The main path's kernels, compiled for a v5e that is described and not
attached (the TPU compiler is installed here; nothing runs). These guard
what the tests on the CPU backend cannot see: what the chip's compiler
refuses, and programs that take it minutes. A compile that passes is not a
chip run — `python chip_smoke.py` on the chip is.

One file on purpose: the process that describes the topology holds the TPU
library until it exits, so every test that needs it lives here, behind one
module-scoped fixture.
"""

import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from trino_tpu import types as T
from trino_tpu.page import Column, Page

SCAN_WIDTH = 1 << 20        # the table cache's page capacity at SF1
BUILD_WIDTH = 1 << 18       # q3's orders build after its filters at SF1
D12_2 = T.DecimalType(12, 2)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _page(sharding, capacity, column_types, lead=()):
    """A Page of shapes (no arrays: a described device holds none)."""
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(lead + shape, dtype, sharding=sharding)
    cols = tuple(Column(spec((capacity,), T.to_numpy_dtype(t)), None, t,
                        None) for t in column_types)
    return Page(cols, spec((), jnp.int32))


def _compile(fn, *args, limit_s):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    took = time.perf_counter() - t0
    assert took < limit_s, \
        f"{took:.0f}s to compile for the v5e (limit {limit_s}s)"
    return compiled


def test_q6_chain_at_scan_width(one_chip):
    """The fused scan-filter-project-aggregate chain (Page.filter inside):
    as a multi-operand sort this took the TPU compiler minutes."""
    import __graft_entry__
    page = _page(one_chip, SCAN_WIDTH, (T.DATE, D12_2, D12_2, D12_2))
    compiled = _compile(__graft_entry__._q6_pipeline(), page, limit_s=60)
    assert " sort(" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 30)


def test_deferred_chain_into_partial_aggregate_moves_no_row(one_chip):
    """q6's shape as the planner composes it (PR 26): filter -> project ->
    partial global aggregate in one chain, the filter deferred to a
    selection mask — no permutation, no gather, no scatter, no sort."""
    from trino_tpu.exec import jit_cache
    from trino_tpu.exec.local_planner import compose_chain
    from trino_tpu.ops import AggSpec, Step, hash_aggregate

    def filt():
        return lambda p, g: p.filter((p.column(0).values >= g[0])
                                     & (p.column(2).values < g[1]))

    def proj():
        return lambda p, g: Page(
            (Column(p.column(3).values * p.column(1).values, None,
                    T.DecimalType(18, 4), None),), p.num_rows)
    specs = (AggSpec("sum", 0, T.DecimalType(18, 4)),)
    steps = ((("filter", "q6 shape"), filt,
              (jnp.int32(8766), jnp.int64(2400))),
             (("project", "q6 shape"), proj, ()))
    tail_key = ("agg-partial", (), specs)
    compose_chain(steps, tail_key,
                  lambda: hash_aggregate((), specs, Step.PARTIAL))
    fn = jit_cache._CACHE[("chain", steps[0][0], steps[1][0], tail_key)][0]
    page = _page(one_chip, SCAN_WIDTH, (T.DATE, D12_2, D12_2, D12_2))
    groups = ((jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
               jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)), ())
    t0 = time.perf_counter()
    compiled = fn.lower(page, groups).compile()
    assert time.perf_counter() - t0 < 60
    text = compiled.as_text()
    for op in (" gather(", " scatter(", " sort("):
        assert op not in text, op
    assert compiled.memory_analysis().temp_size_in_bytes < (64 << 20)


def test_q1_partial_aggregate_reduces_under_slot_masks(one_chip):
    """q1's partial GROUP BY over one scan page (PR 29): twelve slots x
    fifteen states reduce lane-wise under slot masks. The scatter form
    stacked the states into a [lanes, 15] operand the chip tiles to 128
    columns, 1 GB of temporaries a page; the masked form materialises
    nothing as long as the page."""
    from trino_tpu.ops import AggSpec, Step, hash_aggregate
    from trino_tpu.page import Dictionary
    import numpy as np
    page = _page(one_chip, SCAN_WIDTH, (T.VARCHAR, T.VARCHAR, D12_2, D12_2,
                                        D12_2, D12_2, D12_2))
    pools = (Dictionary(np.array(["A", "N", "R"], dtype=object)),
             Dictionary(np.array(["F", "O"], dtype=object)))
    keys = tuple(Column(jax.ShapeDtypeStruct((SCAN_WIDTH,), jnp.int32,
                                             sharding=one_chip),
                        None, T.VARCHAR, pool) for pool in pools)
    page = Page(keys + page.columns[2:], page.num_rows)
    specs = [AggSpec("sum", 2, D12_2), AggSpec("sum", 3, D12_2),
             AggSpec("sum", 4, D12_2), AggSpec("sum", 5, D12_2),
             AggSpec("avg", 2, D12_2), AggSpec("avg", 3, D12_2),
             AggSpec("avg", 6, D12_2), AggSpec("count", None, None)]
    compiled = _compile(hash_aggregate([0, 1], specs, Step.PARTIAL), page,
                        limit_s=60)
    assert compiled.memory_analysis().temp_size_in_bytes < (64 << 20)
    # what scatters is `compact()`, over the twelve slots
    for line in compiled.as_text().splitlines():
        if " scatter(" in line:
            assert str(SCAN_WIDTH) not in line.split(" scatter(")[1], line


def test_q1_chain_walks_sf10s_lineitem_in_one_program(one_chip):
    """q1's chain as the planner composes it — filter, project, the
    direct GROUP BY under slot masks — walking SF10's lineitem inside one
    program (PR 43): seven whole columns of 58 pages x 1 048 576 lanes, a
    `while` over the pages whose body is the page's program. Nothing as
    long as a column is materialised, nothing is gathered or sorted."""
    from trino_tpu.exec import LocalQueryRunner, jit_cache, local_planner
    import chip_smoke
    seen = {}
    real = local_planner.compose_walk

    def spy(pending, tail_key, tail_builder, sample):
        seen.update(pending=pending, tail_key=tail_key,
                    tail_builder=tail_builder, columns=sample.columns)
        return real(pending, tail_key, tail_builder, sample)
    r = LocalQueryRunner.tpch("tiny")
    r.session.set("page_capacity", 8192)
    r.session.set("scan_page_capacity", 8192)
    local_planner.compose_walk = spy
    try:
        r.execute(chip_smoke.Q1)
    finally:
        local_planner.compose_walk = real
    assert r.last_query_stats["chain_walks"] == 1
    pages, lanes = 58, SCAN_WIDTH
    whole = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((pages * lanes,) + x.shape[1:],
                                       x.dtype, sharding=one_chip),
        seen["columns"])
    # the store keeps the four decimals as their low and high words: as
    # int64 operands the chip would split them whole before the loop,
    # 1.95 GB of temporaries
    from trino_tpu.page import SplitColumn
    assert len(whole) == 7
    assert sum(isinstance(c, SplitColumn) for c in whole) == 4
    span = local_planner.ColumnSpan(whole, 59_993_741, lanes, 0, pages)
    assert local_planner.compose_walk(
        seen["pending"], seen["tail_key"], seen["tail_builder"],
        span) is not None
    key = ("chain",) + local_planner.chain_keys(seen["pending"]) + (
        seen["tail_key"] + (("walk", lanes, pages),),)
    fn = jit_cache._CACHE[key][0]
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    groups = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                       sharding=one_chip),
        local_planner.chain_params(seen["pending"]))
    t0 = time.perf_counter()
    compiled = fn.lower(whole, scalar, scalar, groups).compile()
    assert time.perf_counter() - t0 < 120
    text = compiled.as_text()
    assert " while(" in text
    for op in (" gather(", " sort("):
        assert op not in text, op
    # what scatters is `compact()`, over the twelve slots of a page
    for line in text.splitlines():
        if " scatter(" in line:
            assert str(lanes) not in line.split(" scatter(")[1], line
    assert compiled.memory_analysis().temp_size_in_bytes < (64 << 20)


def test_q3_join_build_sort(one_chip):
    """The sort-bearing build kernel of q3's joins: 64-bit keys ordered by
    passes of one 32-bit sort (ops/radix.py)."""
    from trino_tpu.ops.join import prepare_build
    build = _page(one_chip, BUILD_WIDTH, (T.BIGINT, T.DATE, T.INTEGER))
    compiled = _compile(prepare_build([0]), build, limit_s=120)
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 30)


def test_probe_compaction_at_the_kept_rung(one_chip):
    """The join's tight probe compaction (PR 31) at q3's SF10 shape: a
    33 554 432-lane probe buffer of five int64 columns compacted to the
    262 144-lane rung of its 0.5 % matched rows. Seconds to compile; no
    column rides a sort as payload (the one sort is the compiler's own
    lowering of the permutation's int32 scatter, as in `filter`); and no
    gather wider than the rung: the columns are read through the head of
    the permutation only."""
    lanes, rung = 1 << 25, 1 << 18
    page = _page(one_chip, lanes, (T.BIGINT,) * 5)
    mask = jax.ShapeDtypeStruct((lanes,), jnp.bool_, sharding=one_chip)
    compiled = _compile(lambda p, m: p.compact_to(m, rung), page, mask,
                        limit_s=60)
    text = compiled.as_text()
    for line in text.splitlines():
        if " sort(" in line:
            assert "compact_slots/scatter" in line, line
            assert line.split(" sort(")[0].count("[") == 2, line
    gathers = [line for line in text.splitlines() if " gather(" in line]
    assert len(gathers) == 10       # two 32-bit halves a column
    for line in gathers:
        assert f"[{lanes}]" not in line.split(" gather(")[0], line
    out = jax.eval_shape(lambda p, m: p.compact_to(m, rung), page, mask)
    assert out.capacity == rung and len(out.columns) == 5
    # the permutation, the running count and the outputs; never a second
    # copy of the 1.3 GB buffer
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 30)


def test_unique_dense_lookup_is_one_gather(one_chip):
    """`join__uprobe` against the table of build rows (PR 38) at q3's
    larger SF10 shape — a 33 554 432-lane lineitem buffer, the orders
    build's 15 M keys in a table of 16 777 216 slots: exactly ONE gather
    under `join__probe_lookup`, of 32-bit indices into the int32 table;
    no sort (the `search` lookup's four, with its two scatters and three
    gathers, are what this replaces) and no second gather through the
    sort permutation. The row channel leaves as int32."""
    from trino_tpu.ops.join import (build_dense_table, prepare_build,
                                    unique_inner_probe)
    lanes, slots = 1 << 25, 1 << 24
    build = _page(one_chip, 1 << 21, (T.BIGINT, T.DATE, T.INTEGER))
    prepared = jax.eval_shape(prepare_build([0]), build)
    table = jax.eval_shape(build_dense_table(slots), prepared[1],
                           prepared[3], prepared[8], prepared[2])
    assert table.shape == (slots,) and table.dtype == jnp.int32
    probe = _page(one_chip, lanes, (T.BIGINT, D12_2, D12_2, T.DATE))
    op = unique_inner_probe([0], [0], lookup="dense")
    compiled = _compile(op, probe, prepared + (table,), limit_s=60)
    text = compiled.as_text()
    gathers = [line for line in text.splitlines() if " gather(" in line]
    assert len(gathers) == 1, gathers
    assert "join__probe_lookup" in gathers[0]
    assert gathers[0].split(" gather(")[0].split(" = ")[1] \
        .startswith(f"s32[{lanes}]"), gathers[0]
    assert " sort(" not in text and " scatter(" not in text
    pre, found, count = jax.eval_shape(op, probe, prepared + (table,))
    assert pre.columns[-1].values.dtype == jnp.int32
    assert found.shape == (lanes,) and count.dtype == jnp.int64
    # the index, the gathered rows and the mask: no copy of the buffer
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 29)


@pytest.mark.parametrize("lanes, columns, nullable", [
    pytest.param(SCAN_WIDTH, 4, False, id="q3-chain-4xbigint"),
    pytest.param(SCAN_WIDTH, 4, True, id="q3-chain-one-nullable"),
    pytest.param(1 << 25, 5, False, id="verified-probe-33M-5xbigint")])
def test_filter_compacts_with_no_index(one_chip, lanes, columns, nullable):
    """`Page.filter`'s shift-and-select compaction (PR 35) at q3's chain
    filter shape (one scan page of four BIGINT columns) and at the widest
    page any site filters inside a program (ops/join.py's verified probes:
    33 554 432 lanes x 5 BIGINT): seconds to compile, and no gather, no
    scatter and no sort in the compiled program — a row moves by selects
    between an array and itself shifted by a static power of two. Every
    array runs its rounds alone, one round at a time
    (`optimization_barrier`), so the temporaries are two copies of a
    column and of `d` and its bits, not of the page (all arrays a round,
    overlapped: three copies, 3.36 GB at 33 M x 5): under half the
    operand and half a GB, well inside ISSUE 35's twice the operand and
    half a GB — `sf10-throughput-s3` runs at 16.0 of the chip's 16.9 GB
    and a refused allocation there is a failed request."""
    page = _page(one_chip, lanes, (T.BIGINT,) * columns)
    mask = jax.ShapeDtypeStruct((lanes,), jnp.bool_, sharding=one_chip)
    if nullable:
        first = page.columns[0].with_valid(mask)
        page = Page((first,) + page.columns[1:], page.num_rows)
    compiled = _compile(lambda p, m: p.filter(m), page, mask, limit_s=60)
    text = compiled.as_text()
    for op in (" gather(", " scatter(", " sort("):
        assert op not in text, op
    operand = lanes * (8 * columns + nullable)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < operand // 2 + (1 << 29)


def test_mesh_all_to_all_on_four_chips(topo):
    """One mesh program for the four described chips: the hash
    repartition exchange must stay a collective inside the program."""
    from trino_tpu.parallel.exchange import all_to_all_by_key
    from trino_tpu.parallel.mesh import QueryMesh
    mesh = QueryMesh(topo.devices[:4])
    sharded = NamedSharding(mesh.mesh, P(QueryMesh.AXIS))
    page = _page(sharded, 1 << 15, (T.BIGINT, D12_2), lead=(4,))
    compiled = _compile(
        mesh.shard_map(lambda p: all_to_all_by_key(p, [0], 1 << 13)),
        page, limit_s=150)
    assert "all-to-all" in compiled.as_text()


# ------------------------------------------------- q1 and q3 on four chips

SF30_PAGE = 1 << 22         # scan_page_capacity: a shard is whole pages


def _mesh_programs(topo, sql):
    """[(program, params, pages)] — the co-scheduled mesh programs of
    `sql` at TPC-H SF30 under PARTITIONED, as `MeshLowerer` composes them
    and in the order they run, for four described chips: shapes in the place of the resident shards
    (`exec/table_cache.ShardedTable`: 11 pages of 4 194 304 lanes of
    lineitem a chip)."""
    from trino_tpu.connector import tpch
    from trino_tpu.exec.distributed import (DistributedQueryRunner,
                                            _find_remote)
    from trino_tpu.exec.mesh_exec import MeshLowerer, _Env
    from trino_tpu.parallel.mesh import QueryMesh
    from trino_tpu.planner.optimizer import fragment_plan
    from trino_tpu.sql.parser import parse_statement
    mesh = QueryMesh(topo.devices[:4])
    sharded = NamedSharding(mesh.mesh, P(QueryMesh.AXIS))
    replicated = NamedSharding(mesh.mesh, P())
    runner = DistributedQueryRunner.tpch("sf30", devices=jax.devices()[:4])
    runner.session.set("join_distribution_type", "PARTITIONED")
    frag = fragment_plan(runner._plan_for_execution(parse_statement(sql)))

    def scan_page(scan):
        table = scan.table.name.table
        rows = -(-tpch.table_row_count(table, 30.0) // 4)
        lanes = -(-rows // SF30_PAGE) * SF30_PAGE
        cols = []
        for _, ch in scan.assignments:
            pool = tpch.table_dictionary(table, 30.0, ch.name) \
                if T.is_string(ch.type) else None
            dtype = jnp.int32 if pool is not None \
                else T.to_numpy_dtype(ch.type)
            cols.append(Column(jax.ShapeDtypeStruct(
                (4, lanes), dtype, sharding=sharded), None, ch.type, pool))
        return Page(tuple(cols), jax.ShapeDtypeStruct(
            (4,), jnp.int32, sharding=sharded))

    def compose(child, remote, exchange=True):
        """The program of `child` and, before it, those of the fragments
        under it that key on a string and feed it their page."""
        lowerer = MeshLowerer(runner.session, runner.metadata, 4, ())
        top = lowerer.lower_child(child, remote, exchange)
        fed, pages = [], []
        for leaf in lowerer.leaves:
            if not isinstance(leaf, tuple):
                pages.append(scan_page(leaf))
                continue
            fed += compose(*leaf, exchange=False)
            program, params, leaves = fed[-1]
            page = jax.eval_shape(program, params, *leaves)[0]
            pages.append(jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=sharded), page))
        params = tuple(jax.ShapeDtypeStruct(v.shape, v.dtype,
                                            sharding=replicated)
                       for v in lowerer.param_values)

        def per_shard(params, *pages):
            env = _Env(pages, {}, params)
            return top(env), env.aux
        return fed + [(mesh.shard_map(per_shard, replicated=1), params,
                       pages)]
    return [program for child in frag.children for program in compose(
        child, _find_remote(frag.root, child.fragment_id))]


def test_q1_mesh_program_at_the_sf30_shard_shape(topo):
    """q1 over a 46 M-lane shard a chip: the chain under the partial
    aggregate runs 1 048 576 lanes at a time in one loop (whole, its
    stacked scatter-add asked the chip for 23.6 GB), its filter moves no
    row, and the four chips' partial states meet in a collective."""
    import chip_smoke
    (program, params, pages), = _mesh_programs(topo, chip_smoke.Q1)
    assert pages[0].columns[0].values.shape == (4, 11 * SF30_PAGE)
    compiled = _compile(program, params, *pages, limit_s=120)
    text = compiled.as_text()
    # (the compiler spells an all-gather of a few rows as all-reduces)
    assert ("all-gather" in text or "all-reduce" in text) \
        and " while(" in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < (5 << 30)
    assert memory.argument_size_in_bytes < (3 << 30)


@pytest.mark.slow
def test_q3_mesh_program_at_the_sf30_shard_shape(topo):
    """q3 under PARTITIONED over the SF30 shards: seven exchanges, two
    joins, GROUP BY and the partial TopN in one program. Not tier-1: the
    TPU compiler takes about seven minutes over it here (its sorts of
    46 M lanes), and a cold start of the benchmark's four-chip cell pays
    the like once. Run by hand before a four-chip call:
    `pytest tests/test_tpu_compile.py -m slow`."""
    import chip_smoke
    (small, sparams, customer), (program, params, pages) = _mesh_programs(
        topo, chip_smoke.Q3)
    assert [p.columns[0].values.shape[1] // SF30_PAGE
            for p in pages + customer] == [11, 3, 1, 1]
    # customer's filter keys on the SEGMENT: a program of its own, which
    # a new SEGMENT compiles in seconds, and which moves no row
    assert pages[2].selection is not None
    assert "all-to-all" not in _compile(small, sparams, *customer,
                                        limit_s=60).as_text()
    compiled = _compile(program, params, *pages, limit_s=1500)
    text = compiled.as_text()
    assert "all-to-all" in text and "all-gather" in text
    memory = compiled.memory_analysis()
    # what the program holds beside the 2.7 GB of resident shards
    assert memory.temp_size_in_bytes + memory.output_size_in_bytes \
        < (9 << 30)


# ------------------------------------- Q18 and Q4 at SF10 (PR 37)
#
# The shapes are the ones the chip ran (step 0 of PR 37): lineitem is one
# scan page of 60 030 976 lanes, Q18's partial states tighten to 2^24
# lanes for its 15 000 000 groups, Q4's build keeps the scan page's lanes
# for its 37 927 020 late lines.

LINEITEM_LANES = 60_030_976
GROUP_LANES = 1 << 24
DEVICE_BUDGET = 12 << 30            # ISSUE 37: `peak_hbm_GB` under 12


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes)


@pytest.mark.parametrize("step", ["final", "intermediate"])
def test_q18_final_aggregate_at_15m_groups(one_chip, step):
    """GROUP BY l_orderkey, the merge steps: 2^24 lanes of (key, sum,
    count) states through the sorted path (past `_DIRECT_MAX_GROUPS`):
    radix passes, boundary scan, then the reduce as a segmented scan over
    the sorted lanes and one shift compaction (PR 40) — no scatter as
    long as the page but `aggregate__key_gather`'s int32 row index.
    INTERMEDIATE is the same merge emitting states (the executor's
    over-budget compaction)."""
    from trino_tpu.ops import AggSpec, hash_aggregate
    from trino_tpu.ops.aggregate import get_aggregate
    spec = AggSpec("sum", 1, D12_2)
    states = get_aggregate("sum", D12_2).state(D12_2)
    page = _page(one_chip, GROUP_LANES,
                 (T.BIGINT,) + tuple(s.type for s in states))
    op = hash_aggregate([0], [spec], step,
                        [list(range(1, 1 + len(states)))])
    compiled = _compile(op, page, limit_s=300)
    assert _device_bytes(compiled) < DEVICE_BUDGET
    _assert_no_state_scatter(compiled, GROUP_LANES)
    _assert_orders_only_where_it_must(compiled)


def _assert_orders_only_where_it_must(compiled):
    """The sorted GROUP BY holds ONE `conditional` (PR 45): the branch for
    lanes that arrive in key order computes nothing — no sort, no gather,
    no scatter, no fusion: it hands its operands on —, the other holds the
    radix passes and the gathers, and the rounds — the reduce's, the
    compaction's, the keys' move — are compiled once, outside both (the
    compiler may sink an elementwise op of what follows into them: the
    `not` of the dead flags is)."""
    import re
    text = compiled.as_text()
    computations, name = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = line.split(" ")[0 if line[0] == "%" else 1].lstrip("%")
            computations[name] = []
        elif name is not None:
            computations[name].append(line)

    def reached(root):
        seen, todo = set(), [root]
        while todo:
            at = todo.pop()
            if at in seen or at not in computations:
                continue
            seen.add(at)
            for line in computations[at]:
                todo += re.findall(
                    r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", line)
        return [line for at in seen for line in computations[at]]
    conds = [line for line in text.splitlines() if " conditional(" in line]
    assert len(conds) == 1, len(conds)
    branches = [reached(b.strip().lstrip("%")) for b in re.search(
        r"branch_computations=\{([^}]*)\}", conds[0]).group(1).split(",")]
    assert len(branches) == 2
    sorting = [b for b in branches if any(" sort(" in line for line in b)]
    as_they_are = [b for b in branches if b not in sorting]
    assert len(sorting) == 1 and len(as_they_are) == 1
    for word in (" sort(", " gather(", " scatter(", " fusion(", " select("):
        assert not any(word in line for line in as_they_are[0]), word
    assert any(" gather(" in line for line in sorting[0])
    once = ("aggregate__segment_reduce", "aggregate__compact_shift",
            "aggregate__key_move")
    for scope in once:
        assert scope in text, scope
        for branch in branches:
            assert not any(scope in line for line in branch), scope


def _assert_no_state_scatter(compiled, lanes):
    """No scatter over the page's lanes moves anything 64 bits wide: the
    state columns are scanned and shifted, and what is still scattered is
    the 32-bit row index of each group's first lane."""
    for line in compiled.as_text().splitlines():
        if " scatter(" in line:
            result = line.split(" = ")[1].split(" scatter(")[0]
            assert not (f"[{lanes}]" in result
                        and result.startswith(("s64", "u64", "f64"))), line


def test_q18_partial_aggregate_over_the_lineitem_page(one_chip):
    """The same GROUP BY's partial step over the whole scan page: 60 M
    lanes in, one state row a group, the states reduced by the segmented
    scan (26 rounds at this capacity) and moved by shifts."""
    from trino_tpu.ops import AggSpec, Step, hash_aggregate
    page = _page(one_chip, LINEITEM_LANES, (T.BIGINT, D12_2))
    op = hash_aggregate([0], [AggSpec("sum", 1, D12_2)], Step.PARTIAL)
    compiled = _compile(op, page, limit_s=300)
    assert _device_bytes(compiled) < DEVICE_BUDGET
    _assert_no_state_scatter(compiled, LINEITEM_LANES)
    _assert_orders_only_where_it_must(compiled)


def test_q18_probe_of_60m_lanes_against_a_hundred_orders(one_chip):
    """Q18's outer join: every lineitem lane looked up in a build of the
    ~100 orders the HAVING kept, whose keys span the table — the router's
    `search` side, with no prefilter to help."""
    from trino_tpu.ops.join import prepare_build, unique_inner_probe
    build = _page(one_chip, 1024, (T.BIGINT, T.BIGINT, T.VARCHAR,
                                   D12_2, T.DATE))
    prepared = jax.eval_shape(prepare_build([0]), build)
    probe = _page(one_chip, LINEITEM_LANES, (T.BIGINT, D12_2))
    compiled = _compile(unique_inner_probe([0], [0], lookup="search"),
                        probe, prepared, limit_s=300)
    assert _device_bytes(compiled) < DEVICE_BUDGET


def test_q4_semi_join_build_at_the_lineitem_page(one_chip):
    """EXISTS builds on the filtering source: the late lineitems' keys,
    collected into one page of the scan's 60 M lanes and sorted once
    (`join__semi_build`), then a direct-address table over the 15 M
    order keys they span."""
    from trino_tpu.ops.join import build_dense_table, prepare_build
    build = _page(one_chip, LINEITEM_LANES, (T.BIGINT,))
    compiled = _compile(prepare_build([0], semi=True), build, limit_s=420)
    assert _device_bytes(compiled) < DEVICE_BUDGET
    assert "join__semi_build" in compiled.as_text()

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    table = _compile(build_dense_table(GROUP_LANES, semi=True),
                     spec((LINEITEM_LANES,), jnp.uint64),
                     spec((), jnp.int32), spec((), jnp.uint64), limit_s=120)
    assert _device_bytes(table) < DEVICE_BUDGET


def test_q4_semi_join_probe_of_a_quarters_orders(one_chip):
    """The quarter's ~570 000 orders (2^20 lanes after the date filter)
    probe that table: one gather a lane, then `Page.filter`'s shift
    rounds over the three columns (ops/join.py's SEMI site)."""
    from trino_tpu.ops.join import (JoinType, build_dense_table, hash_join,
                                    prepare_build)
    build = _page(one_chip, LINEITEM_LANES, (T.BIGINT,))
    prepared = jax.eval_shape(prepare_build([0], semi=True), build)
    table = jax.eval_shape(build_dense_table(GROUP_LANES, semi=True),
                           prepared[1], prepared[3], prepared[8])
    probe = _page(one_chip, 1 << 20, (T.BIGINT, T.DATE, T.VARCHAR))
    op = hash_join([0], [0], JoinType.SEMI, output_capacity=1 << 20,
                   prepared=True, lookup="dense", null_aware=False)
    compiled = _compile(op, probe, prepared + (table,), limit_s=120)
    text = compiled.as_text()
    assert "join__semi_probe" in text
    assert " sort(" not in text
    assert _device_bytes(compiled) < DEVICE_BUDGET


Q4_BUILD = (T.BIGINT, T.DATE, T.DATE)   # l_orderkey and the two dates


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_q4_set_build_orders_no_lane(one_chip):
    """The EXISTS' build since PR 46: a pass of reductions over the
    collected page's 60 M lanes (the keys' bounds, the rows, whether a
    key is NULL), then ONE scatter of a constant from the keys as they
    arrive into the 2^24 slots the 15 M order keys span — no radix pass
    over 64-bit keys, no permutation, no gather, and no temporary as long as the page but the
    key's own halves."""
    from trino_tpu.ops.join import build_set_table, semi_build_stats
    build = _page(one_chip, LINEITEM_LANES, Q4_BUILD)
    stats = _compile(semi_build_stats([0]), build, limit_s=120)
    text = stats.as_text()
    assert "join__semi_build" in text
    for op in (" sort(", " gather(", " scatter("):
        assert op not in text, op
    # the key column's two 32-bit halves (the TPU has no 64-bit lanes)
    # and nothing else as long as the page
    halves = 2 * 4 * LINEITEM_LANES + (1 << 20)
    assert stats.memory_analysis().temp_size_in_bytes < halves
    table = _compile(build_set_table([0], GROUP_LANES), build,
                     _spec(one_chip, (), jnp.uint64), limit_s=120)
    text = table.as_text()
    assert "join__semi_build" in text
    assert "join__radix" not in text and " gather(" not in text
    scatters = [line for line in text.splitlines() if " scatter(" in line]
    assert len(scatters) == 1, scatters
    assert scatters[0].split(" scatter(")[0].split(" = ")[1] \
        .startswith(f"s32[{GROUP_LANES}]"), scatters[0]
    # the compiler lowers a scatter over unsorted indices to ONE sort of
    # the int32 slot indices with their updates and a scatter in slot
    # order (whatever `unique_indices` says; the position table's scatter
    # had the same one): that sort is the scatter's own, the only one
    sorts = [line for line in text.splitlines() if " sort(" in line]
    assert len(sorts) == 1, sorts
    assert "join__semi_build/scatter" in sorts[0]
    assert sorts[0].split(" sort(")[0].split(" = ")[1].startswith(
        f"(s32[{LINEITEM_LANES}]"), sorts[0]
    assert "s64[" not in sorts[0] and "u64[" not in sorts[0]
    # the slot index is formed where it is scattered: the sorted build
    # held keys, dead flags, permutation and run lengths of 60 M lanes
    assert table.memory_analysis().temp_size_in_bytes < halves
    assert _device_bytes(table) < DEVICE_BUDGET


@pytest.mark.parametrize("join_type, scope", [
    ("semi", "join__semi_probe"), ("anti", "join__semi_probe"),
    ("mark", "join__mark_probe")])
def test_q4_set_probe_is_one_gather(one_chip, join_type, scope):
    """The quarter's orders against the set table: exactly ONE gather a
    probe lane, an int32 slot for each of the 2^20 lanes, under the
    join's own scope — no second gather for a run length, no sort, no
    scatter; then `Page.filter`'s shift rounds (a MARK join moves
    nothing)."""
    from trino_tpu.ops.join import (build_set_table, semi_build_stats,
                                    set_semi_join)
    build = _page(one_chip, LINEITEM_LANES, Q4_BUILD)
    kmin, _kmax, n_rows, has_null = jax.eval_shape(
        semi_build_stats([0]), build)
    table, key_cols = jax.eval_shape(
        build_set_table([0], GROUP_LANES), build, kmin)
    assert table.shape == (GROUP_LANES,)
    lanes = 1 << 20
    probe = _page(one_chip, lanes, (T.BIGINT, T.DATE, T.VARCHAR))
    compiled = _compile(
        set_semi_join([0], join_type, null_aware=join_type != "semi"),
        probe, (table, kmin, n_rows, has_null, key_cols), limit_s=120)
    text = compiled.as_text()
    gathers = [line for line in text.splitlines() if " gather(" in line]
    assert len(gathers) == 1, gathers
    assert scope in gathers[0]
    assert gathers[0].split(" gather(")[0].split(" = ")[1] \
        .startswith(f"s32[{lanes}]"), gathers[0]
    assert " sort(" not in text and " scatter(" not in text
    assert _device_bytes(compiled) < DEVICE_BUDGET


# ---- Q9 at SF10 (PR 42): six lineitem columns through part's row table,
# their compaction, and the join on (partkey, suppkey) -------------------

Q9_PROBE_LANES = 1 << 24        # a coalesced lineitem buffer: 2^29 B / 48 B
Q9_LINES = 1 << 21              # the ~1.3 M lines of a COLOR's parts
LINEITEM_Q9 = (T.BIGINT, T.BIGINT, T.BIGINT, D12_2, D12_2, D12_2)


def test_q9_probe_of_a_lineitem_buffer_through_parts_row_table(one_chip):
    """Q9's first join: a buffer of 16 777 216 lineitem lanes carrying the
    six columns the query reads, looked up by `l_partkey` in the table of
    build rows over part's 2 000 000 keys (2^21 slots; the build the ~43 K
    parts a COLOR keeps): one gather, no sort, no scatter, as q3's."""
    from trino_tpu.ops.join import (build_dense_table, prepare_build,
                                    unique_inner_probe)
    build = _page(one_chip, 1 << 16, (T.BIGINT,))
    prepared = jax.eval_shape(prepare_build([0]), build)
    table = jax.eval_shape(build_dense_table(1 << 21), prepared[1],
                           prepared[3], prepared[8], prepared[2])
    probe = _page(one_chip, Q9_PROBE_LANES, LINEITEM_Q9)
    op = unique_inner_probe([1], [0], lookup="dense")
    compiled = _compile(op, probe, prepared + (table,), limit_s=120)
    text = compiled.as_text()
    assert len([ln for ln in text.splitlines() if " gather(" in ln]) == 1
    assert " sort(" not in text and " scatter(" not in text
    assert _device_bytes(compiled) < DEVICE_BUDGET


def test_q9_compaction_carries_six_columns_to_the_kept_rung(one_chip):
    """The 2.16 % of that buffer's lanes whose part matched, six columns
    and the build row, gathered to the 524 288-lane rung
    (`join__probe_compact`): no gather as wide as the buffer."""
    rung = 1 << 19
    page = _page(one_chip, Q9_PROBE_LANES, LINEITEM_Q9 + (T.INTEGER,))
    mask = jax.ShapeDtypeStruct((Q9_PROBE_LANES,), jnp.bool_,
                                sharding=one_chip)
    compiled = _compile(lambda p, m: p.compact_to(m, rung), page, mask,
                        limit_s=120)
    for line in compiled.as_text().splitlines():
        if " gather(" in line:
            assert f"[{Q9_PROBE_LANES}]" not in line.split(" gather(")[0]
    assert _device_bytes(compiled) < DEVICE_BUDGET


@pytest.mark.slow
def test_q9_composite_search_and_verify_at_partsupps_lanes(one_chip):
    """The join on (ps_partkey, ps_suppkey) = (l_partkey, l_suppkey) as
    the fixed plan gives it: partsupp's 8 000 000 rows in one buffer of
    8 388 608 lanes probe a build of the matching lines (2^21 lanes, six
    columns, ~7 lines a pair: duplicates, so the expanding `hash_join`).
    The key is mix-hashed to 64 bits: `search`, the expansion, and
    `join__composite_verify` over every candidate, each in the program."""
    from trino_tpu.ops.join import JoinType, hash_join, prepare_build
    lanes = 1 << 23
    build = _page(one_chip, Q9_LINES, LINEITEM_Q9)
    prepared = jax.eval_shape(prepare_build([1, 2]), build)
    probe = _page(one_chip, lanes, (T.BIGINT, T.BIGINT, D12_2))
    op = hash_join([0, 1], [1, 2], JoinType.INNER, output_capacity=lanes,
                   prepared=True, lookup="search", probe_out=(2,),
                   build_out=(0, 2, 3, 4, 5))
    compiled = _compile(op, probe, prepared, limit_s=420)
    text = compiled.as_text()
    for scope in ("join__probe_lookup", "join__probe_expand",
                  "join__composite_verify", "join__output_gather"):
        assert scope in text, scope
    # marked slow: 143 s to compile for the v5e alone, 244 s beside
    # tier-1's other compiles, where it pushed Q18's 60 M-lane probe past
    # its own 300 s limit (here, PR 42); 1.16 GB on the device;
    # the build's own program — `prepare_build([1, 2])` at these lanes,
    # 34 s and 0.28 GB — is q3's build sort with the hash mixed in
    assert _device_bytes(compiled) < DEVICE_BUDGET


# ---- Q13 at SF10 (PR 44): 1.5 M customers LEFT JOIN their 15 M orders —
# the outer join's build, its position table, the expanding probe with its
# null-extension, and the GROUP BY over the 2^24 joined lanes ------------

Q13_CUSTOMER_LANES = 1 << 21    # customer's 1 500 000 rows, one probe page
Q13_ORDER_LANES = 1 << 24       # the 14.9 M kept orders: the build, and
#                                 the 15.4 M joined rows of the output
ORDERS_Q13 = (T.BIGINT, T.BIGINT, T.VARCHAR)    # orderkey, custkey, comment


def test_q13_outer_join_build_and_its_position_table(one_chip):
    """The LEFT join's build: the kept orders in one page of 2^24 lanes
    (the comment's codes ride along), sorted once on `o_custkey` — ten
    rows a key — and a direct-address table of sorted POSITIONS over the
    1.5 M customer keys they span (2^21 slots: `_prepare_probe` decides
    `dense`, the fill rule holds at a tenth of a slot a lane)."""
    from trino_tpu.ops.join import build_dense_table, prepare_build
    build = _page(one_chip, Q13_ORDER_LANES, ORDERS_Q13)
    compiled = _compile(prepare_build([1]), build, limit_s=300)
    assert _device_bytes(compiled) < DEVICE_BUDGET

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    table = _compile(build_dense_table(Q13_CUSTOMER_LANES),
                     spec((Q13_ORDER_LANES,), jnp.uint64),
                     spec((), jnp.int32), spec((), jnp.uint64), limit_s=120)
    assert _device_bytes(table) < DEVICE_BUDGET


@pytest.mark.parametrize("capacity", [
    Q13_CUSTOMER_LANES,
    # 128 s to compile for the v5e beside tier-1's other compiles
    pytest.param(Q13_ORDER_LANES, marks=pytest.mark.slow)],
    ids=["first-capacity", "rerun"])
def test_q13_left_join_expands_customers_to_their_orders(one_chip,
                                                         capacity):
    """The customer page probes that table (one gather a lane, no sort for
    the lookup), expands ten-fold and null-extends a third of its rows:
    first at the probe's own capacity, where the total overflows, then at
    2^24 (`_run_with_overflow`: `probe_overflow_reruns` 1). The
    null-extension reads as `join__outer_fill`."""
    from trino_tpu.ops.join import (JoinType, build_dense_table, hash_join,
                                    prepare_build)
    build = _page(one_chip, Q13_ORDER_LANES, ORDERS_Q13)
    prepared = jax.eval_shape(prepare_build([1]), build)
    table = jax.eval_shape(build_dense_table(Q13_CUSTOMER_LANES),
                           prepared[1], prepared[3], prepared[8])
    probe = _page(one_chip, Q13_CUSTOMER_LANES, (T.BIGINT,))
    op = hash_join([0], [1], JoinType.LEFT, output_capacity=capacity,
                   prepared=True, lookup="dense", probe_out=(0,),
                   build_out=(0,))
    compiled = _compile(op, probe, prepared + (table,), limit_s=300)
    text = compiled.as_text()
    for scope in ("join__probe_lookup", "join__probe_expand",
                  "join__outer_fill", "join__output_gather"):
        assert scope in text, scope
    assert _device_bytes(compiled) < DEVICE_BUDGET


def test_q13_count_by_customer_over_the_joined_lanes(one_chip):
    """`count(o_orderkey) GROUP BY c_custkey` over the join's 2^24 output
    lanes, the order key NULL on the null-extended ones: the sorted GROUP
    BY (1.5 M groups), its states reduced by the segmented scan."""
    from trino_tpu.ops import AggSpec, Step, hash_aggregate
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    lanes = Q13_ORDER_LANES
    page = Page((Column(spec((lanes,), jnp.int64), None, T.BIGINT, None),
                 Column(spec((lanes,), jnp.int64), spec((lanes,), jnp.bool_),
                        T.BIGINT, None)), spec((), jnp.int32))
    op = hash_aggregate([0], [AggSpec("count", 1, T.BIGINT)], Step.PARTIAL)
    compiled = _compile(op, page, limit_s=300)
    assert _device_bytes(compiled) < DEVICE_BUDGET
    _assert_no_state_scatter(compiled, lanes)
    _assert_orders_only_where_it_must(compiled)
