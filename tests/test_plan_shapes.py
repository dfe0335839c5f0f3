"""Plan-shape regression tests — BasePlanTest-style matchers.

Reference parity: sql/planner/assertions/BasePlanTest.java:49 +
PlanMatchPattern.java — assert optimizer OUTPUT SHAPE (join order, predicate
pushdown, TopN formation, exchange placement, partial/final aggregation
split) over EXPLAIN text, so optimizer changes in later rounds cannot
silently regress plan quality. The text matchers parse the plan printer's
indented tree into (depth, op, detail) rows.
"""

import re

import pytest

from trino_tpu.exec import LocalQueryRunner

from tpch_sql import PASSING, QUERIES


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner.tpch("tiny")


class PlanText:
    """Indented plan-printer output as a queryable node list."""

    LINE = re.compile(r"^(\s*)- (\w+)(\[(.*)\])?$")

    def __init__(self, text: str):
        self.text = text
        self.nodes = []                      # (depth, op, detail)
        for line in text.splitlines():
            m = self.LINE.match(line)
            if m:
                depth = len(m.group(1)) // 3
                self.nodes.append((depth, m.group(2), m.group(4) or ""))

    def ops(self):
        return [op for _, op, _ in self.nodes]

    def find(self, op, detail_substr=""):
        return [(d, o, det) for d, o, det in self.nodes
                if o == op and detail_substr in det]

    def has(self, op, detail_substr=""):
        return bool(self.find(op, detail_substr))

    def parent_of(self, op, detail_substr=""):
        """The node one level above the first match."""
        for i, (d, o, det) in enumerate(self.nodes):
            if o == op and detail_substr in det:
                for j in range(i - 1, -1, -1):
                    if self.nodes[j][0] == d - 1:
                        return self.nodes[j]
        return None

    def children_of(self, index):
        d = self.nodes[index][0]
        out = []
        for j in range(index + 1, len(self.nodes)):
            if self.nodes[j][0] <= d:
                break
            if self.nodes[j][0] == d + 1:
                out.append((j, self.nodes[j]))
        return out

    def real_cross_joins(self):
        """Cross joins EXCEPT the scalar-subquery broadcast pattern (a cross
        against EnforceSingleRow is how scalar subqueries decorrelate)."""
        out = []
        for i, (d, o, det) in enumerate(self.nodes):
            if o == "Join" and "cross" in det:
                kids = [n for _, n in self.children_of(i)]
                if not any(op == "EnforceSingleRow" for _, op, _ in kids):
                    out.append((d, o, det))
        return out


def plan(runner, sql) -> PlanText:
    """Single-tree logical plan (fragment boundaries reset indentation, so
    shape assertions use TYPE LOGICAL; distributed shape uses dplan)."""
    return PlanText(
        runner.execute("EXPLAIN (TYPE LOGICAL) " + sql).only_value())


# ------------------------------------------------------------- join order

@pytest.mark.parametrize("name", PASSING)
def test_no_cross_joins(runner, name):
    """EliminateCrossJoins / ReorderJoins: every TPC-H plan is cross-free."""
    p = plan(runner, QUERIES[name][0])
    assert not p.real_cross_joins(), \
        f"{name} has a cross join:\n{p.text}"


def test_q3_builds_topn_not_sort_limit(runner):
    p = plan(runner, QUERIES["q3"][0])
    assert p.has("TopN")
    assert not p.has("Sort"), "ORDER BY+LIMIT must fuse into TopN"


# ------------------------------------------------------ predicate pushdown

def test_filter_pushed_to_scan_q6(runner):
    p = plan(runner, QUERIES["q6"][0])
    assert not p.has("Join")
    # the only Filter sits directly above the lineitem scan
    filters = p.find("Filter")
    assert len(filters) == 1
    d, _, det = filters[0]
    assert "l_shipdate" in det or "shipdate" in det
    below = [n for n in p.nodes if n[0] == d + 1]
    assert any(op == "TableScan" and "lineitem" in detail
               for _, op, detail in below)


def test_dimension_filter_pushed_below_join(runner):
    sql = ("SELECT n_name FROM nation, region "
           "WHERE n_regionkey = r_regionkey AND r_name = 'EUROPE'")
    p = plan(runner, sql)
    # the region filter must sit under the join (build side), not above it
    f = p.find("Filter", "EUROPE")
    assert f, p.text
    joins = p.find("Join")
    assert joins and f[0][0] > joins[0][0], \
        f"filter not pushed below join:\n{p.text}"


# ------------------------------------------------------- semi joins / exists

def test_in_subquery_forms_semijoin(runner):
    sql = ("SELECT count(*) FROM orders WHERE o_custkey IN "
           "(SELECT c_custkey FROM customer)")
    p = plan(runner, sql)
    assert p.has("SemiJoin")


# -------------------------------------------------------- distributed shape

def dplan(runner, sql) -> str:
    return runner.execute(
        "EXPLAIN (TYPE DISTRIBUTED) " + sql).only_value()


def test_q1_distributed_splits_partial_final(runner):
    text = dplan(runner, QUERIES["q1"][0])
    assert "Aggregation[partial" in text
    assert "Aggregation[final" in text
    assert "RemoteSource" in text
    # partial agg and final agg live in different fragments
    frag_of = {}
    current = None
    for line in text.splitlines():
        m = re.match(r"\s*Fragment (\d+)", line)
        if m:
            current = int(m.group(1))
        if "Aggregation[partial" in line:
            frag_of["partial"] = current
        if "Aggregation[final" in line:
            frag_of["final"] = current
    assert frag_of["partial"] != frag_of["final"]


def test_broadcast_join_replicates_small_side(runner):
    text = dplan(runner,
                 "SELECT count(*) FROM orders, customer "
                 "WHERE o_custkey = c_custkey")
    assert "replicated" in text


def test_partitioned_join_repartitions_both_sides(runner):
    runner.execute("SET SESSION join_distribution_type = 'PARTITIONED'")
    try:
        text = dplan(runner,
                     "SELECT count(*) FROM orders, customer "
                     "WHERE o_custkey = c_custkey")
    finally:
        runner.execute("RESET SESSION join_distribution_type")
    assert "partitioned" in text
    assert text.count("RemoteSource") >= 2


def test_distinct_agg_not_split(runner):
    text = dplan(runner,
                 "SELECT o_orderpriority, count(DISTINCT o_orderstatus) "
                 "FROM orders GROUP BY o_orderpriority")
    assert "Aggregation[partial" not in text
    assert "Aggregation[single" in text


# ------------------------------------------------------------ join ordering

SCHEMAS = ("tiny", "sf1", "sf10", "sf30", "sf100")
_RUNNERS = {}


def runner_at(schema: str) -> LocalQueryRunner:
    """A runner over a TPC-H schema of any scale: plans only, no data is
    generated until a query runs."""
    if schema not in _RUNNERS:
        _RUNNERS[schema] = LocalQueryRunner.tpch(schema)
    return _RUNNERS[schema]


@pytest.mark.parametrize("schema", SCHEMAS)
def test_q9_join_order_starts_from_part(schema):
    """At every scale factor Q9's innermost join is lineitem against the
    LIKE-filtered part, and its six tables meet in five joins with no
    cross join among them. (Until PR 42 the guard ran at `tiny` alone and
    asked only for five joins and no cross; at `sf10` the plan was
    supplier x part, 4.3e9 rows: a constant cross-join penalty against
    costs that grow with the scale, and a two-column key damped like two
    independent edges.)"""
    p = plan(runner_at(schema), QUERIES["q9"][0])
    assert len(p.find("Join")) == 5
    assert not p.has("Join", "cross")
    first = p.parent_of("Filter", "like(p_name")
    assert first is not None and first[1] == "Join", p.text
    assert "l_partkey" in first[2] and "p_partkey" in first[2]
    assert " AND " not in first[2]
    at = p.nodes.index(first)
    kids = [node for _, node in p.children_of(at)]
    assert [op for _, op, _ in kids] == ["TableScan", "Filter"], p.text
    assert kids[0][2].endswith(".lineitem")
    # nothing sits below it but the two scans: it is where the plan starts
    deepest = max(d for d, op, _ in p.nodes if op == "Join")
    assert first[0] == deepest
    # the two-column key joins partsupp to those lines, not to lineitem
    composite = p.find("Join", " AND ")
    assert len(composite) == 1 and composite[0][0] == deepest - 1


@pytest.mark.parametrize("schema", ["sf10", "sf100"])
@pytest.mark.parametrize("name", sorted(QUERIES))
def test_connected_joins_never_cross(schema, name):
    """`joins_connected_never_cross` (server/app.CAPABILITIES):
    `reorder_joins` compares plans by their cross joins first and by
    cost second, so none of the 22 queries plans one at the scales users
    run (a scalar subquery's single row is attached by a cross join: not
    one of these)."""
    p = plan(runner_at(schema), QUERIES[name][0])
    assert not p.real_cross_joins(), f"{name} at {schema}:\n{p.text}"


@pytest.mark.parametrize("name", ["q3", "q4", "q18"])
def test_the_benchmarks_sf10_plans_print_as_on_the_parent(name):
    """q3's, Q18's and Q4's SF10 plans, as `EXPLAIN` printed them before
    PR 42 changed the join reordering (tests/golden_plans_sf10.json)."""
    import json
    import os
    with open(os.path.join(os.path.dirname(__file__),
                           "golden_plans_sf10.json")) as f:
        want = json.load(f)[name]
    got = "\n".join(row[0] for row in runner_at("sf10").execute(
        "EXPLAIN " + QUERIES[name][0]).rows)
    assert got == want


def _q13_sql(word1: str = "special", word2: str = "packages") -> str:
    """The benchmark's own Q13 text (benchmark/queries/q13.py)."""
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "queries", "q13.py")
    with open(path) as f:
        text = f.read()
    sql = text.split('SQL = """', 1)[1].split('"""', 1)[0]
    return sql.format(word1=word1, word2=word2)


@pytest.mark.parametrize("schema", SCHEMAS)
def test_q13_not_like_is_pushed_below_the_outer_join(schema):
    """Q13's `o_comment NOT LIKE '%WORD1%WORD2%'` is written in the LEFT
    join's ON clause, on the null-supplying side: at every scale the
    optimizer pushes it onto the orders scan, where it is a chain filter
    whose LIKE table is an operand (`like_pattern_operand`) — never the
    join's residual filter, which the executor refuses on an outer join
    and where a pattern is still static (ROADMAP M4). Customer, the
    preserved side, is the probe; orders the build."""
    p = plan(runner_at(schema), _q13_sql())
    joins = p.find("Join")
    assert len(joins) == 1 and joins[0][2].startswith("left;"), p.text
    assert "like" not in joins[0][2] and "o_comment" not in joins[0][2]
    assert "c_custkey" in joins[0][2] and "o_custkey" in joins[0][2]
    kids = [node for _, node in p.children_of(p.nodes.index(joins[0]))]
    assert [op for _, op, _ in kids] == ["TableScan", "Filter"], p.text
    assert kids[0][2].endswith(".customer")
    assert kids[1][2].startswith("not(like(o_comment")
    assert "'%special%packages%'" in kids[1][2]
    under = p.children_of(p.nodes.index(kids[1]))
    assert [n[1] for _, n in under] == ["TableScan"]
    assert under[0][1][2].endswith(".orders")
    # an aggregate over an aggregate: count(o_orderkey) by customer, then
    # the customers by that count
    aggs = p.find("Aggregation")
    assert any("count(o_orderkey" in det for _, _, det in aggs), p.text
    assert any("keys=(count" in det for _, _, det in aggs), p.text


def test_q13_sf10_plan_prints_as_pinned():
    """Q13's SF10 plan as `EXPLAIN` prints it (PR 44: the cell
    `sf10-power-q13` runs this plan; tests/golden_plans_sf10.json)."""
    import json
    import os
    with open(os.path.join(os.path.dirname(__file__),
                           "golden_plans_sf10.json")) as f:
        want = json.load(f)["q13"]
    got = "\n".join(row[0] for row in runner_at("sf10").execute(
        "EXPLAIN " + _q13_sql()).rows)
    assert got == want


def test_q21_exists_and_not_exists_shape(runner):
    p = plan(runner, QUERIES["q21"][0])
    # EXISTS -> semi/mark machinery without cross joins
    assert not p.has("Join", "cross")
