"""Program and scope names (PR 25): every jitted program is named from its
cache key, `<family>__<tag>[_<tag>...]`, and every operator phase inside a
program carries a `jax.named_scope` of the same grammar — what a device
trace needs to say whose time a kernel's is (`benchmark/trace_programs.py`
reads both back)."""

import re

import jax
import jax.numpy as jnp
import pytest

from trino_tpu import types as T
from trino_tpu.exec import LocalQueryRunner, jit_cache
from trino_tpu.page import Page

import chip_smoke

Q6 = chip_smoke.Q6.format(date="1994-01-01", disc="0.06", qty=24)

# every tag a cache key (or a chain step) leads with today, and the family
# it must land in: a new tag nobody listed comes out as `misc__<tag>`
KNOWN_TAGS = {
    "scan_filter": ["filter", "project", "select", "dconcat", "unnest",
                    "unnest-count", "assign-unique-id", "tpch-generate",
                    "tpch-generate-pooled", "tpch-generate-oidx",
                    "page-cut", "page-cut-tail"],
    "aggregate": ["agg-partial", "agg-bypass", "agg-final",
                  "agg-intermediate", "agg-single", "agg-groupmax",
                  "agg-spill-part", "agg-having"],
    "join": ["join", "join-prep", "join-spill-part", "uprobe", "uattach",
             "semijoin", "markjoin", "cross-attach",
             "dense-table", "dense-table-rows", "dfbounds", "dfrange",
             "dfrange-mask", "probe-compact", "spill-prep", "spill-probe",
             "spill-probe-dense", "semijoin-prep",
             "semijoin-dense-table", "semijoin-stats",
             "semijoin-set-table", "join-composite", "uprobe-composite",
             "join-prep-composite", "join-outer", "join-prep-outer",
             "join-outer-composite", "join-prep-outer-composite",
             "join-full-outer", "dense-table-outer"],
    "sort": ["sort", "sort-spill-bounds", "sort-spill-part",
             "sort-spill-rank", "topn-masked", "topn", "merge-sort"],
    "window": ["window"],
    "exchange": ["exchange-a2a", "exchange-gather", "mesh-prog",
                 "mesh-sconcat"],
}
# tags left to `misc` on purpose: none today
KNOWN_MISC = set()


@pytest.fixture(scope="module")
def runner():
    r = LocalQueryRunner.tpch("tiny")
    for sql in (Q6, chip_smoke.Q1, chip_smoke.Q3):
        r.execute(sql)
    return r


def test_every_cached_program_has_a_grammar_name(runner):
    """After q6, q1 and q3 every jit-cache entry — this worker's earlier
    tests' too — is named by the grammar, and the function jax.jit was
    given carries that name (it becomes the module's, `jit_<name>`)."""
    with jit_cache._LOCK:
        entries = list(jit_cache._CACHE.items())
    assert len(entries) >= 20
    for key, entry in entries:
        name = jit_cache.program_name(key)
        assert jit_cache.NAME_GRAMMAR.match(name), (key[:1], name)
        assert entry[0].__name__ == name, (entry[0].__name__, name)
        assert entry[0].__name__ not in ("run", "op", "prep", "<lambda>")
    names = {jit_cache.program_name(k) for k, _ in entries}
    assert {"aggregate__chain_filter_project_agg_partial",
            "aggregate__agg_final", "join__join_prep", "join__uprobe",
            "join__uattach", "sort__topn_masked"} <= names, sorted(names)


def test_q6_q1_q3_leave_no_misc_program(monkeypatch):
    """Every kernel the three queries look up, hit or miss, has a family."""
    from trino_tpu.obs.stats import QueryStatsCollector
    seen = set()
    monkeypatch.setattr(QueryStatsCollector, "jit_hit",
                        lambda self, key=None: seen.add(key))
    monkeypatch.setattr(QueryStatsCollector, "jit_miss",
                        lambda self, key=None: seen.add(key))
    tpch = LocalQueryRunner.tpch("tiny")
    for sql in (Q6, chip_smoke.Q1, chip_smoke.Q3):
        tpch.execute(sql)
    names = sorted({jit_cache.program_name(k) for k in seen})
    assert len(names) >= 15, names
    assert [n for n in names if n.startswith("misc__")] == [], names


@pytest.mark.parametrize("family", sorted(KNOWN_TAGS))
def test_known_tags_have_their_family(family):
    for tag in KNOWN_TAGS[family]:
        assert jit_cache.family_of(tag) == family, tag
        name = jit_cache.program_name((tag, ("payload", 1)))
        assert name == f"{family}__{tag.replace('-', '_')}"


def test_unknown_tags_are_misc_and_listed():
    assert jit_cache.program_name(("brand-new-kernel", 3)) \
        == "misc__brand_new_kernel"
    assert jit_cache.program_name((("nested", 1), 2)) == "misc__nested"
    assert jit_cache.program_name(()) == "misc__untagged"
    assert KNOWN_MISC == set()


def test_names_carry_no_literals_and_stay_bounded():
    """Tags only: two chains that differ in expression text, literals or
    column numbers share one name."""
    a = ("chain", ("filter", "l_quantity < 24", 7), ("project", (1, 2)),
         ("agg-partial", (0,), ("sum", 3)))
    b = ("chain", ("filter", "l_discount > 0.05", 9), ("project", (4,)),
         ("agg-partial", (1, 2), ("avg", 5)))
    assert jit_cache.program_name(a) == jit_cache.program_name(b) \
        == "aggregate__chain_filter_project_agg_partial"
    # a chain takes the family of its blocking tail, else scan_filter
    assert jit_cache.program_name(("chain", ("filter", 1), ("project", 2))) \
        == "scan_filter__chain_filter_project"
    assert jit_cache.program_name(
        ("chain", ("project", 1), ("topn-masked", 2))) \
        == "sort__chain_project_topn_masked"


def _chain_text(tail="agg-partial"):
    """Lowered text of a filter -> project [-> aggregate tail] chain built
    the way the planner builds it (compose_chain). `tail`: `agg-partial`,
    `agg-bypass` or None (a plain chain, as `iter_pages` composes)."""
    from trino_tpu.exec.local_planner import compose_chain
    from trino_tpu.ops import AggSpec, Step, hash_aggregate
    from trino_tpu.ops.aggregate import passthrough_partial
    from trino_tpu.page import Column

    def filt():
        return lambda page, params: page.filter(
            page.column(0).values < params[0])

    def proj():
        return lambda page, params: Page(
            (page.column(0), Column(page.column(1).values * 2, None,
                                    T.BIGINT, None)), page.num_rows)
    specs = [AggSpec("sum", 1, T.BIGINT)]
    pending = ((("filter", "x"), filt, (jnp.int64(5),)),
               (("project", "y"), proj, ()))
    key = ("chain", ("filter", "x"), ("project", "y"))
    if tail is None:
        compose_chain(pending)
    else:
        tail_key = (tail, (0,), "sum")
        key += (tail_key,)
        compose_chain(pending, tail_key, {
            "agg-partial": lambda: hash_aggregate([0], specs, Step.PARTIAL),
            "agg-bypass": lambda: passthrough_partial([0], specs)}[tail])
    fn = jit_cache._CACHE[key][0]
    page = Page.from_numpy(
        [jnp.arange(64) % 7, jnp.arange(64)], [T.BIGINT, T.BIGINT])
    lowered = fn.lower(page, ((jnp.int64(5),), ()))
    return lowered.as_text(debug_info=True)


def test_chain_steps_and_tail_each_have_a_scope():
    text = _chain_text()
    assert "jit(aggregate__chain_filter_project_agg_partial)/" in text
    for scope in ("scan_filter__filter", "scan_filter__project",
                  "aggregate__agg_partial"):
        assert f"/{scope}/" in text, scope
    # the partial aggregate takes the filter's mask as a selection: the
    # chain compacts nothing (PR 26); the sorted reduce works out once who
    # moves where and moves its own states to a lane a group (PR 40), and
    # the keys with them (PR 45)
    for tag in ("compact_gather", "compact_slots", "compact_shift"):
        assert text.count(tag) == sum(text.count(
            f"aggregate__{under}/aggregate__{tag}")
            for under in ("group_bounds", "segment_reduce")), tag
    assert "compact_gather" not in text
    assert "aggregate__key_move/aggregate__key_move" in text
    assert "aggregate__key_gather" not in text
    # shared kernels take the family of the operator that called them;
    # the sort is one branch of the order test's `cond` (PR 45)
    assert "aggregate__agg_partial/aggregate__group_sort/" \
           "aggregate__order_test" in text
    assert re.search(r"aggregate__agg_partial/aggregate__group_sort/cond/"
                     r"branch_\d_fun/aggregate__radix_pass", text)
    assert re.search(r"aggregate__group_sort/cond/branch_\d_fun/"
                     r"aggregate__radix_gather", text)
    assert "sort__radix_pass" not in text
    for scope in set(re.findall(r"(?<=/)[a-z_]+__[a-z0-9_]+(?=/)", text)):
        assert jit_cache.NAME_GRAMMAR.match(scope), scope


def test_the_walking_chain_keeps_the_chains_name_and_scopes():
    """The program that walks its scan's pages inside (PR 43) is the chain
    under the chain's name, each step under its scope inside the loop: a
    device trace gives its seconds to the owners it gave a page's launch."""
    from trino_tpu.exec.local_planner import (ColumnSpan, compose_chain,
                                              compose_walk)
    from trino_tpu.ops import AggSpec, Step, hash_aggregate
    from trino_tpu.page import Column

    def filt():
        return lambda page, params: page.filter(
            page.column(0).values < params[0])

    def proj():
        return lambda page, params: Page(
            (Column(page.column(1).values * 2, None, T.BIGINT, None),),
            page.num_rows)
    specs = (AggSpec("sum", 0, T.BIGINT),)
    pending = ((("filter", "x"), filt, (jnp.int64(5),)),
               (("project", "y"), proj, ()))
    tail_key = ("agg-partial", (), specs)

    def tail():
        return hash_aggregate((), specs, Step.PARTIAL)
    whole = Page.from_numpy([jnp.arange(256) % 7, jnp.arange(256)],
                            [T.BIGINT, T.BIGINT]).columns
    span = ColumnSpan(whole, 200, 64, 0, 4)
    walk = compose_walk(pending, tail_key, tail, span)
    out = walk(span)
    assert int(out.num_rows) == 4           # a state row a page
    per_page = compose_chain(pending, tail_key, tail)
    want = sum(int(per_page(Page(tuple(
        Column(c.values[i * 64:(i + 1) * 64], None, c.type, None)
        for c in whole), min(64, 200 - i * 64))).columns[0].values[0])
        for i in range(4))
    assert int(jnp.sum(out.columns[0].values[:4])) == want
    key = ("chain", ("filter", "x"), ("project", "y"),
           tail_key + (("walk", 64, 4),))
    assert jit_cache.program_name(key) \
        == "aggregate__chain_filter_project_agg_partial"
    # the compiled program's `op_name`s: what the device trace reads. The
    # loop's body is a function of its own in the lowered text, and its
    # scopes join the program's path only when XLA inlines it
    names = set(re.findall(r'op_name="([^"]*)"', jit_cache._CACHE[key][0]
                           .lower(whole, jnp.int32(0), jnp.int32(200),
                                  ((jnp.int64(5),), ())).compile().as_text()))
    root = "jit(aggregate__chain_filter_project_agg_partial)/"
    assert any(n.startswith(f"{root}while/body/") for n in names), names
    for scope in ("scan_filter__filter", "scan_filter__project",
                  "aggregate__agg_partial/aggregate__global_reduce"):
        assert any(n.startswith(f"{root}while/body/closed_call/{scope}/")
                   for n in names), scope
    # the steps compact nothing; what compacts is the pages' state rows
    text = "\n".join(sorted(names))
    assert "scan_filter__compact" not in text
    assert f"{root}aggregate__compact_shift/" in text
    for scope in set(re.findall(r"(?<=/)[a-z_]+__[a-z0-9_]+(?=/)", text)):
        assert jit_cache.NAME_GRAMMAR.match(scope), scope
    # a GROUP BY that sorts holds a state a lane: it does not walk
    assert compose_walk((), ("agg-partial", (0,), specs),
                        lambda: hash_aggregate((0,), specs, Step.PARTIAL),
                        span) is None
    # nor does a chain that does not end in the partial aggregate
    assert compose_walk(pending, ("agg-bypass", (), specs), tail,
                        span) is None


@pytest.mark.parametrize("tail, program", [
    (None, "scan_filter__chain_filter_project"),
    ("agg-bypass", "aggregate__chain_filter_project_agg_bypass")])
def test_chains_without_a_partial_aggregate_tail_still_compact(tail,
                                                                program):
    """A plain chain's page leaves its program and the bypass tail emits a
    state row per input row under num_rows: both need the live rows as a
    prefix, so their filter keeps its compaction, under the filter's
    scope and family."""
    text = _chain_text(tail)
    assert f"jit({program})/" in text
    assert "scan_filter__filter/scan_filter__compact_slots" in text
    assert "scan_filter__filter/scan_filter__compact_shift" in text
    # `filter` moves rows by shift-and-select (PR 35): no index
    assert "compact_gather" not in text
    assert "stablehlo.gather" not in text
    assert "stablehlo.scatter" not in text and "stablehlo.sort" not in text


def test_scopes_do_not_change_the_program():
    """Scopes are metadata: the HLO a scoped function lowers to is the
    unscoped function's, name for name, once locations are left out."""
    from trino_tpu.page import op_scope

    def plain(x):
        return jnp.cumsum(x * 2).sum()

    def scoped(x):
        with op_scope("aggregate__segment_reduce"):
            return jnp.cumsum(x * 2).sum()
    x = jnp.arange(128)
    a = jax.jit(plain).lower(x).as_text()
    b = jax.jit(scoped).lower(x).as_text()
    assert a.replace("jit_plain", "F") == b.replace("jit_scoped", "F")


def test_a_mesh_program_names_every_op(monkeypatch):
    """Inside `exchange__mesh_prog` (PR 28) every lowering and every
    collective helper runs under a `<family>__<tag>` scope, partitioning
    apart from the collective itself, so a device trace can tell whose
    second a kernel's is: q3 under PARTITIONED carries exchange, join,
    aggregate and scan scopes, and no op of the per-shard body lies
    outside one."""
    from trino_tpu.exec import mesh_exec
    from trino_tpu.exec.distributed import DistributedQueryRunner
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from jax.experimental.compilation_cache import compilation_cache
    calls = []
    run_program = mesh_exec._run_program

    def spy(runner, top_fn, staged, struct_key, ladder, params):
        calls.append((struct_key + (tuple(sorted(ladder.items())),),
                      params, staged))
        return run_program(runner, top_fn, staged, struct_key, ladder,
                           params)
    monkeypatch.setattr(mesh_exec, "_run_program", spy)
    runner = DistributedQueryRunner.tpch("tiny", devices=jax.devices()[:4])
    runner.session.set("join_distribution_type", "PARTITIONED")
    runner.execute(chip_smoke.Q3)
    # customer's filter keys on a string and is a program of its own
    # first; the one with the joins in it is the last
    assert len(calls) == 2
    key, params, staged = calls[-1]
    assert jit_cache.program_name(key) == "exchange__mesh_prog"
    # compiled here and now: an executable read back from the persistent
    # cache has lost most of its op names
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jit_cache._CACHE[key][0].lower(params, *staged).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    names = set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))
    # the per-shard body's ops: all but the arguments and what shard_map
    # itself lays around the body (its own name, broadcasts of constants)
    body = set()
    for name in names:
        path = [s for s in name.split("/")
                if not (s.startswith("jit(") or s == "shard_map")]
        if path and not name.startswith("args[") \
                and not re.fullmatch(r"broadcast\.\d+", "/".join(path)):
            body.add("/".join(path))
    assert len(body) > 100
    for scope in ("exchange__partition", "exchange__all_to_all",
                  "exchange__compact", "exchange__broadcast",
                  "exchange__heavy_keys", "exchange__psum",
                  "join__hash_join", "join__probe_lookup",
                  "aggregate__partial", "scan_filter__filter",
                  "scan_filter__project"):
        assert any(scope in n.split("/") for n in body), scope
    # q3's three filters feed hash repartitions, which drop dead rows as
    # they bucket them: none compacts its page first
    for tag in ("compact_gather", "compact_slots", "compact_shift"):
        assert not any(f"scan_filter__{tag}" in n for n in body), tag
    # (XLA keeps a few ops of inner jits — `cummin`'s windows — under
    # their primitive's bare name: too few to matter to a trace)
    bare = {n for n in body
            if "/" not in n and not jit_cache.NAME_GRAMMAR.match(n)}
    assert len(bare) <= 0.02 * len(body), sorted(bare)
    for name in body - bare:
        scopes = [s for s in name.split("/")
                  if jit_cache.NAME_GRAMMAR.match(s)]
        assert scopes, f"no scope on {name}"
        # the collectives lie under their own tags, the work that
        # prepares rows for them and unpacks them under others
        op = name.rsplit("/", 1)[-1]
        if op.startswith(("all_to_all", "all_gather")):
            assert scopes[-1] in ("exchange__all_to_all",
                                  "exchange__broadcast",
                                  "exchange__all_gather"), name
        if op.startswith(("sort", "scatter", "gather", "cumsum")):
            assert scopes[-1] not in ("exchange__all_to_all",
                                      "exchange__broadcast"), name


# ---------------------------------------- semi and mark joins (PR 37)

SEMI_SCOPES = ("join__semi_probe", "join__mark_probe", "join__semi_build")


def _join_text(join_type, semi, key=None):
    """Lowered text of one join over a prepared build, built from the
    operators the planner builds it from (`_prepare_build` and
    `_exec_semijoin_filter` / `_exec_SemiJoinNode` / `_exec_JoinNode`),
    under the program name `key` gives (default: an INNER or semi
    join's)."""
    from trino_tpu.ops.join import hash_join, prepare_build

    def run(probe, build):
        prepared = prepare_build([0], semi)(build)
        return hash_join([0], [0], join_type, prepared=True)(probe, prepared)
    probe = Page.from_numpy([jnp.arange(64) % 7, jnp.arange(64)],
                            [T.BIGINT, T.BIGINT])
    build = Page.from_numpy([jnp.arange(16) % 5], [T.BIGINT])
    key = key or ("semijoin" if semi else "join", join_type)
    return jax.jit(jit_cache.named(run, key)).lower(probe, build) \
        .as_text(debug_info=True)


@pytest.mark.parametrize("join_type, probe_scope", [
    ("semi", "join__semi_probe"), ("anti", "join__semi_probe"),
    ("mark", "join__mark_probe")])
def test_semi_and_mark_joins_carry_scopes_of_their_own(join_type,
                                                       probe_scope):
    """A trace tells a semi, anti or mark join's lookup and build from an
    inner join's: their scopes are their own, under a program of the
    `join__semijoin` / `join__markjoin` names."""
    text = _join_text(join_type, semi=True)
    assert "jit(join__semijoin)/" in text
    assert f"/{probe_scope}/" in text
    assert "/join__semi_build/" in text
    # the build's radix passes are the family's shared kernel, under it
    assert "join__semi_build/join__radix_pass" in text
    for scope in ("join__probe_lookup", "join__build_sort",
                  "join__build_runs"):
        assert scope not in text, scope
    for scope in set(re.findall(r"(?<=/)[a-z_]+__[a-z0-9_]+(?=/)", text)):
        assert jit_cache.NAME_GRAMMAR.match(scope), scope


@pytest.mark.parametrize("join_type, program, probe_scope", [
    ("semi", "semijoin", "join__semi_probe"),
    ("anti", "semijoin", "join__semi_probe"),
    ("mark", "markjoin", "join__mark_probe")])
def test_the_set_tables_programs_read_as_the_semi_joins_own(
        join_type, program, probe_scope):
    """The set build's two programs and the set probe (PR 46) under the
    names the executor gives them: `join__semijoin_stats` and
    `join__semijoin_set_table` hold nothing but `join__semi_build`, the
    probe is `join__semijoin` / `join__markjoin` with its lookup under
    `join__semi_probe` / `join__mark_probe` — every name begins
    `join__semi` or `join__mark`, so `semijoin_device_ms_per_q` (a prefix
    match) keeps reading the work — and no radix pass is in any of them."""
    from trino_tpu.ops.join import (build_set_table, semi_build_stats,
                                    set_semi_join)
    probe = Page.from_numpy([jnp.arange(64) % 7, jnp.arange(64)],
                            [T.BIGINT, T.BIGINT])
    build = Page.from_numpy([jnp.arange(16) % 5], [T.BIGINT])
    stats_key = ("semijoin-stats", (0,))
    table_key = ("semijoin-set-table", (0,), 8)
    assert jit_cache.program_name(stats_key) == "join__semijoin_stats"
    assert jit_cache.program_name(table_key) == "join__semijoin_set_table"
    stats_op = jax.jit(jit_cache.named(semi_build_stats([0]), stats_key))
    table_op = jax.jit(jit_cache.named(build_set_table([0], 8), table_key))
    kmin, _kmax, n_rows, has_null = stats_op(build)
    table, key_cols = table_op(build, kmin)
    probe_op = jax.jit(jit_cache.named(
        set_semi_join([0], join_type), (program, (0,), (0,))))
    texts = {
        "join__semijoin_stats": stats_op.lower(build),
        "join__semijoin_set_table": table_op.lower(build, kmin),
        f"join__{program}": probe_op.lower(
            probe, (table, kmin, n_rows, has_null, key_cols))}
    for name, lowered in texts.items():
        text = lowered.as_text(debug_info=True)
        assert f"jit({name})/" in text
        scopes = set(re.findall(r"(?<=/)[a-z_]+__[a-z0-9_]+(?=/)", text))
        assert scopes, name
        for scope in scopes:
            assert jit_cache.NAME_GRAMMAR.match(scope), scope
            assert scope.startswith(("join__semi", "join__mark",
                                     "join__compact")), (name, scope)
        if name == f"join__{program}":
            assert probe_scope in scopes
            assert "join__semi_build" not in scopes
        else:
            assert scopes == {"join__semi_build"}
        assert "join__radix" not in text and "stablehlo.sort" not in text


@pytest.mark.parametrize("join_type", ["inner", "left", "semi"])
def test_a_join_on_two_columns_carries_names_of_its_own(join_type):
    """A key of more than one column (Q9's (partkey, suppkey), PR 42) is
    mix-hashed, searched and verified: the executor names its programs
    `join__join_composite` / `join__uprobe_composite` /
    `join__join_prep_composite` (`local_planner._composite`), and the
    verification of every candidate has the scope
    `join__composite_verify`, beside the expansion's `join__probe_expand`."""
    from trino_tpu.exec.local_planner import _composite
    from trino_tpu.ops.join import hash_join, prepare_build
    assert _composite("join", (0, 1)) == "join-composite"
    assert _composite("uprobe", (3,)) == "uprobe"
    assert jit_cache.program_name((_composite("join-prep", (0, 1)), (0, 1))) \
        == "join__join_prep_composite"

    def run(probe, build):
        prepared = prepare_build([0, 1])(build)
        return hash_join([0, 1], [0, 1], join_type, prepared=True)(
            probe, prepared)
    probe = Page.from_numpy([jnp.arange(64) % 7, jnp.arange(64) % 3],
                            [T.BIGINT, T.BIGINT])
    build = Page.from_numpy([jnp.arange(16) % 5, jnp.arange(16) % 3],
                            [T.BIGINT, T.BIGINT])
    key = (_composite("join", (0, 1)), join_type)
    text = jax.jit(jit_cache.named(run, key)).lower(probe, build) \
        .as_text(debug_info=True)
    assert "jit(join__join_composite)/" in text
    assert "/join__composite_verify/" in text
    assert "/join__probe_expand/" in text
    for scope in set(re.findall(r"(?<=/)[a-z_]+__[a-z0-9_]+(?=/)", text)):
        assert jit_cache.NAME_GRAMMAR.match(scope), scope
    # one key column: hashing is the identity, nothing to verify
    assert "join__composite_verify" not in _join_text(
        "inner" if join_type == "semi" else join_type, semi=False)


@pytest.mark.parametrize("join_type", ["left", "full"])
def test_an_outer_join_carries_names_of_its_own(join_type):
    """A LEFT or FULL join (Q13's customer LEFT JOIN orders, PR 44): the
    executor names its programs `join__join_outer` / `join__join_prep_outer`
    / `join__dense_table_outer` / `join__join_full_outer`
    (`local_planner._composite(..., outer=True)`), and what makes it outer
    — the one slot an unmatched probe row emits, the null-extension of the
    build columns — has the scope `join__outer_fill`, inside the
    expansion and the output gather."""
    from trino_tpu.exec.local_planner import _composite
    assert _composite("join", (0,), outer=True) == "join-outer"
    assert _composite("join", (0, 1), outer=True) == "join-outer-composite"
    assert _composite("join", (0,)) == "join"
    for tag, name in (("join", "join__join_outer"),
                      ("join-prep", "join__join_prep_outer"),
                      ("join-full", "join__join_full_outer")):
        assert jit_cache.program_name(
            (_composite(tag, (0,), outer=True), (0,))) == name
    assert jit_cache.program_name(("dense-table-outer", 1024)) \
        == "join__dense_table_outer"
    key = (_composite("join", (0,), outer=True), join_type)
    text = _join_text(join_type, semi=False, key=key)
    assert "jit(join__join_outer)/" in text
    assert "/join__probe_expand/join__outer_fill/" in text
    assert "/join__output_gather/join__outer_fill/" in text
    for scope in set(re.findall(r"(?<=/)[a-z_]+__[a-z0-9_]+(?=/)", text)):
        assert jit_cache.NAME_GRAMMAR.match(scope), scope


def test_an_inner_join_keeps_its_names():
    """The ledger's `breakdown` of the cells that were there reads as
    before: an INNER join's program is `join__join`, and nothing in it is
    under `join__outer_fill`."""
    text = _join_text("inner", semi=False)
    assert "jit(join__join)/" in text
    assert "outer" not in text


def test_an_outer_join_through_the_executor_is_named(runner):
    """LEFT and FULL joins as the executor runs them: every program of
    theirs has `outer` in its name, and the inner join beside them does
    not."""
    from trino_tpu.obs.stats import QueryStatsCollector
    seen = set()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(QueryStatsCollector, "jit_hit",
                   lambda self, key=None: seen.add(key))
        mp.setattr(QueryStatsCollector, "jit_miss",
                   lambda self, key=None: seen.add(key))
        tpch = LocalQueryRunner.tpch("tiny")
        tpch.execute("SELECT count(o_orderkey), count(*) FROM customer "
                     "LEFT JOIN orders ON c_custkey = o_custkey")
        left = {jit_cache.program_name(k) for k in seen}
        seen.clear()
        tpch.execute("SELECT count(o_orderkey), count(c_custkey) FROM "
                     "customer FULL JOIN orders ON c_custkey = o_custkey")
        full = {jit_cache.program_name(k) for k in seen}
        seen.clear()
        tpch.execute("SELECT count(*) FROM customer JOIN orders "
                     "ON c_custkey = o_custkey")
        inner = {jit_cache.program_name(k) for k in seen}
    assert {"join__join_outer", "join__join_prep_outer",
            "join__dense_table_outer"} <= left, sorted(left)
    assert {"join__join_full_outer", "join__join_prep_outer"} <= full, \
        sorted(full)
    for names in (left, full):
        joins = {n for n in names if n.startswith("join__")}
        assert joins and all("outer" in n for n in joins), sorted(joins)
    assert {n for n in inner if "outer" in n} == set(), sorted(inner)
    assert "join__join_prep" in inner


def test_unique_composite_probe_verifies_under_the_scope():
    from trino_tpu.ops.join import prepare_build, unique_inner_probe

    def run(probe, build):
        return unique_inner_probe([0, 1], [0, 1])(
            probe, prepare_build([0, 1])(build))
    probe = Page.from_numpy([jnp.arange(64) % 7, jnp.arange(64) % 3],
                            [T.BIGINT, T.BIGINT])
    build = Page.from_numpy([jnp.arange(16), jnp.arange(16) % 3],
                            [T.BIGINT, T.BIGINT])
    text = jax.jit(jit_cache.named(run, ("uprobe-composite",))) \
        .lower(probe, build).as_text(debug_info=True)
    assert "jit(join__uprobe_composite)/" in text
    assert "join__probe_lookup/join__composite_verify" in text


def test_like_table_is_an_activity_with_an_annotation():
    """The host's build of a LIKE table is the activity `like_table`: a
    name of ACTIVITIES, `host__like_table` under a profiler session, and
    the counter `like_tables_built` in the snapshot."""
    from trino_tpu.obs.stats import ACTIVITIES, QueryStatsCollector
    assert "like_table" in ACTIVITIES
    tpch = LocalQueryRunner.tpch("tiny")
    tpch.execute("SELECT count(*) FROM part WHERE p_name LIKE '%green%'")
    stats = tpch.last_query_stats
    assert stats["like_tables_built"] == 1
    assert stats["host_calls"]["like_table"] == 1
    assert stats["cross_joins"] == 0
    assert stats["probe_lookup_lanes_search"] == 0
    for key in ("like_tables_built", "cross_joins",
                "probe_lookup_lanes_search"):
        assert QueryStatsCollector().snapshot()[key] == 0


@pytest.mark.parametrize("join_type", ["inner", "left"])
def test_no_inner_join_carries_a_semi_scope(join_type):
    text = _join_text(join_type, semi=False)
    assert "/join__probe_lookup/" in text and "/join__build_sort/" in text
    for scope in SEMI_SCOPES:
        assert scope not in text, scope


def test_the_dense_table_of_a_semi_join_is_its_build():
    from trino_tpu.ops.join import build_dense_table
    args = (jnp.arange(16, dtype=jnp.uint64), jnp.int32(16), jnp.uint64(0))
    semi = jax.jit(build_dense_table(32, True)).lower(*args) \
        .as_text(debug_info=True)
    inner = jax.jit(build_dense_table(32)).lower(*args) \
        .as_text(debug_info=True)
    assert "join__semi_build" in semi
    assert "join__build_dense_table" not in semi
    assert "join__build_dense_table" in inner
    assert "join__semi_build" not in inner


def test_having_is_a_step_of_the_aggregate_family():
    """The filter over an aggregation's output (HAVING) is keyed and
    scoped `agg-having`; any other filter stays `filter`."""
    from trino_tpu.obs.stats import QueryStatsCollector
    seen = set()
    tpch = LocalQueryRunner.tpch("tiny")
    orig_hit, orig_miss = (QueryStatsCollector.jit_hit,
                           QueryStatsCollector.jit_miss)
    try:
        QueryStatsCollector.jit_hit = \
            lambda self, key=None: seen.add(key) or orig_hit(self, key)
        QueryStatsCollector.jit_miss = \
            lambda self, key=None: seen.add(key) or orig_miss(self, key)
        tpch.execute("SELECT l_orderkey FROM lineitem WHERE l_tax > 0.01 "
                     "GROUP BY l_orderkey HAVING sum(l_quantity) > 250")
    finally:
        QueryStatsCollector.jit_hit = orig_hit
        QueryStatsCollector.jit_miss = orig_miss
    names = {jit_cache.program_name(k) for k in seen}
    having = [n for n in names if "agg_having" in n]
    assert having and all(n.startswith("aggregate__chain_agg_having")
                          for n in having), sorted(names)
    assert any(n.startswith("aggregate__chain_filter") for n in names)
    assert jit_cache.program_name((("agg-having", "x"),)) \
        == "aggregate__agg_having"


@pytest.fixture(scope="module")
def tiny_columns():
    from trino_tpu.connector import tpch_gen as G
    rows = G.row_count("lineitem", 0.01)
    return {
        "late": G.numeric_chunk("lineitem", 0.01, "l_commitdate", 0, rows)
        < G.numeric_chunk("lineitem", 0.01, "l_receiptdate", 0, rows),
        "l_orderkey": G.numeric_chunk("lineitem", 0.01, "l_orderkey", 0,
                                      rows),
        "l_quantity": G.numeric_chunk("lineitem", 0.01, "l_quantity", 0,
                                      rows),
        "o_orderdate": G.numeric_chunk("orders", 0.01, "o_orderdate", 0,
                                       15000)}


_Q4 = """
    SELECT o_orderpriority, count(*) FROM orders
    WHERE o_orderdate >= DATE '1993-07-01'
      AND o_orderdate < DATE '1993-07-01' + INTERVAL '3' MONTH
      AND EXISTS (SELECT * FROM lineitem WHERE l_orderkey = o_orderkey
                  AND l_commitdate < l_receiptdate)
    GROUP BY o_orderpriority ORDER BY o_orderpriority"""
_Q18 = """
    SELECT c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
           sum(l_quantity)
    FROM customer, orders, lineitem
    WHERE o_orderkey IN (SELECT l_orderkey FROM lineitem
                         GROUP BY l_orderkey HAVING sum(l_quantity) > 250)
      AND c_custkey = o_custkey AND o_orderkey = l_orderkey
    GROUP BY c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
    ORDER BY o_totalprice DESC, o_orderdate LIMIT 100"""


def test_q4_counts_its_semi_join_and_its_groups(tiny_columns):
    """EXISTS builds on the late lineitems and probes the quarter's
    orders; the final aggregate emits the five priorities."""
    import numpy as np
    tpch = LocalQueryRunner.tpch("tiny")
    tpch.execute(_Q4)
    stats = tpch.last_query_stats
    lo = int(np.datetime64("1993-07-01", "D").astype(np.int64))
    hi = int(np.datetime64("1993-10-01", "D").astype(np.int64))
    dates = tiny_columns["o_orderdate"]
    assert stats["semi_join_build_rows"] == int(tiny_columns["late"].sum())
    assert stats["semi_join_probe_rows"] \
        == int(((dates >= lo) & (dates < hi)).sum())
    assert stats["aggregate_groups_out"] == 5


def test_q18_counts_its_inner_groups_and_the_orders_it_probes(
        tiny_columns):
    """Q18's inner GROUP BY emits every order (15 000 at `tiny`), its
    HAVING keeps a few dozen, the IN probes every order against those,
    and the outer GROUP BY emits one group for each."""
    import numpy as np
    tpch = LocalQueryRunner.tpch("tiny")
    got = tpch.execute(_Q18)
    stats = tpch.last_query_stats
    sums = np.bincount(tiny_columns["l_orderkey"],
                       weights=tiny_columns["l_quantity"])
    kept = int((sums > 25000).sum())
    assert 1 <= kept <= 100 and len(got.rows) == kept
    assert stats["semi_join_build_rows"] == kept
    assert stats["semi_join_probe_rows"] == 15000
    assert stats["aggregate_groups_out"] == 15000 + kept


@pytest.mark.parametrize("sql, row_table, set_table", [
    pytest.param(chip_smoke.Q3, 2, 0, id="q3"),
    pytest.param(_Q4, 0, 1, id="q4"),
    pytest.param(_Q18, 2, 1, id="q18")])
def test_joins_count_their_lookups_and_the_lanes_they_ran_over(
        monkeypatch, sql, row_table, set_table):
    """One `_prepare_probe` decision a join, counted by what the table
    holds (PR 38). q3: both builds are unique, INNER and dense — two
    tables of build rows. Q4's `EXISTS` is a semi join on one column: a
    set table, scattered from lineitem's lanes as they arrive (PR 46).
    Q18 at `tiny`: the customer join and the outer join with lineitem
    (unique, INNER: the ~50 orders the HAVING kept span the order keys,
    inside the row table's slot cap) read row tables, the `IN` a set
    table (at SF10 its hundred keys span 15 M and it is searched);
    nothing reads a position table. `probe_lookup_lanes` is the
    capacities of the buffers those lookups ran over."""
    from trino_tpu.exec.local_planner import LocalExecutionPlanner
    lanes = []
    counted = LocalExecutionPlanner._lookup_lanes

    def spy(self, pages):
        for page in counted(self, pages):
            lanes.append(page.capacity)
            yield page
    monkeypatch.setattr(LocalExecutionPlanner, "_lookup_lanes", spy)
    tpch = LocalQueryRunner.tpch("tiny")
    tpch.execute(sql)
    stats = tpch.last_query_stats
    assert (stats["probe_lookups_row_table"],
            stats["probe_lookups_set_table"],
            stats["probe_lookups_position_table"],
            stats["probe_lookups_search"]) == (row_table, set_table, 0, 0)
    assert (stats["semi_build_lanes_set"] > 0) == bool(set_table)
    assert stats["semi_build_lanes_sorted"] == 0
    assert len(lanes) >= row_table + set_table
    assert stats["probe_lookup_lanes"] == sum(lanes) > 0


@pytest.mark.parametrize("sql, sorted_dispatches", [
    pytest.param(Q6, 0, id="q6"),
    pytest.param(chip_smoke.Q1, 0, id="q1"),
    pytest.param(_Q4, 0, id="q4"),
    pytest.param(_Q18, 2, id="q18")])
def test_the_sorted_reduce_is_counted_where_it_runs(sql, sorted_dispatches):
    """`sorted_reduces_scanned` counts the dispatches of a program whose
    sorted GROUP BY reduced by the segmented scan (PR 40),
    `sorted_reduce_lanes` the capacities of the pages they ran over. q6
    has no GROUP BY, q1 and Q4 group by pooled values (the direct path);
    Q18's inner GROUP BY runs the PARTIAL chain a page and the FINAL
    kernel, its outer one the same again."""
    tpch = LocalQueryRunner.tpch("tiny")
    tpch.execute(sql)
    stats = tpch.last_query_stats
    if sorted_dispatches == 0:
        assert stats["sorted_reduces_scanned"] == 0
        assert stats["sorted_reduce_lanes"] == 0
    else:
        assert stats["sorted_reduces_scanned"] >= sorted_dispatches
        # a power of two a page, at least a lane a dispatch
        assert stats["sorted_reduce_lanes"] \
            >= 1024 * stats["sorted_reduces_scanned"]


@pytest.mark.parametrize("step", ["partial", "final", "intermediate",
                                  "single"])
def test_the_sorted_group_by_scatters_no_state_column(step):
    """The sorted path reduces its state columns (here int64 and float64)
    by a scan over the sorted lanes and moves them by shifts (PR 40), and
    the keys as the states (PR 45): the lowered program's only scatter is
    the boundary flag's first lane, and what it says while it is traced is
    the counter's fact."""
    from trino_tpu.ops import AggSpec, hash_aggregate
    from trino_tpu.page import trace_notes
    specs = [AggSpec("sum", 1, T.BIGINT), AggSpec("avg", 2, T.DOUBLE),
             AggSpec("min", 1, T.BIGINT)]
    page = Page.from_numpy(
        [jnp.arange(64) % 7, jnp.arange(64), jnp.arange(64) * 0.5],
        [T.BIGINT, T.BIGINT, T.DOUBLE])
    chans = None
    if step in ("final", "intermediate"):
        page = jax.eval_shape(hash_aggregate([0], specs, "partial"), page)
        chans = [[1, 2], [3, 4], [5, 6]]
    with trace_notes() as said:
        text = jax.jit(hash_aggregate([0], specs, step, chans)) \
            .lower(page).as_text(dialect="hlo")
    assert said == {"sorted_reduce_scan:64"}
    scatters = [line.split(" = ")[1].split(" scatter(")[0]
                for line in text.splitlines() if " scatter(" in line]
    assert [t.split("[")[0] for t in scatters] == ["pred"], scatters
    assert "s64[64]" in text and "f64[64]" in text


def test_a_cached_kernels_first_call_lies_under_a_compile_span():
    """A `cached_kernel` program compiles inside its first call: that
    call is a `compile` span of the calling query and the next is not;
    the counters of the AOT sites do not move."""
    from trino_tpu.obs.stats import QueryStatsCollector
    key = ("semijoin", "compile-span-test")
    jit_cache._CACHE.pop(key, None)
    col = QueryStatsCollector("q-compile-span")
    jit_cache.set_observer(col)
    try:
        with col.phase("execution"):
            fn = jit_cache.cached_kernel(key, lambda: lambda x: x * 2 + 1)
            assert int(fn(jnp.int32(3))) == 7
            assert int(fn(jnp.int32(4))) == 9
            again = jit_cache.cached_kernel(key, lambda: None)
            assert int(again(jnp.int32(5))) == 11
    finally:
        jit_cache.set_observer(None)
        jit_cache._CACHE.pop(key, None)
    spans = [s for s in col.request_spans() if s[0] == "compile"]
    assert len(spans) == 1
    execution = next(s for s in col.request_spans() if s[0] == "execution")
    assert execution[1] <= spans[0][1] <= spans[0][2] <= execution[2]
    snap = col.snapshot()
    assert snap["jit_compiles"] == 0 and snap["compile_time_ms"] == 0.0
    assert snap["jit_misses"] == 1 and snap["jit_hits"] == 1
