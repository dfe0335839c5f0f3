"""Deferred compaction (PR 26): a fused chain of lane-wise steps that ends in
the partial hash aggregate runs its filters as a selection mask — no
`compact_slots`, no `compact_shift` (until PR 35: `compact_gather`) — and must aggregate exactly the rows
the compacting chain aggregates.

Each case runs the chain three ways and compares row for row:
  deferred  — `compose_chain(steps, agg-partial)` then `agg-final`, the way
              `_exec_AggregationNode` builds it;
  compacted — the same steps as a tail-less chain (its filters compact),
              then the same partial and final aggregates;
  reference — plain Python over the NumPy columns.
`approx_distinct` is single-step (the planner collects its input and no
chain defers for it), so its cases apply SINGLE-step `hash_aggregate` to a
page that a deferred filter left a selection on: the sketch paths read
liveness from `row_mask()` like the others and must honour it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.exec import jit_cache
from trino_tpu.exec.local_planner import (chain_defers_compaction,
                                          compose_chain)
from trino_tpu.ops import AggSpec, Step, hash_aggregate
from trino_tpu.ops.aggregate import get_aggregate
from trino_tpu.page import Column, Dictionary, Page, defer_compaction

CAP = 256
FLAGS = np.array(["A", "N", "R"], dtype=object)
# channels of the scanned page, and of the projected one (a fresh Page
# with one computed column more)
K, FLAG, X, D, M, X2 = range(6)


def _columns(seed=26):
    """NumPy columns at full capacity. Every lane holds data that passes
    the filters, the ones behind num_rows too: only the masks keep them
    out."""
    rng = np.random.default_rng(seed)
    return {
        "k": rng.integers(0, 7, CAP).astype(np.int64) * 1_000_000_007,
        "flag": rng.integers(0, 3, CAP).astype(np.int32),
        "flag_valid": rng.random(CAP) > 0.15,
        "x": rng.integers(-500, 500, CAP).astype(np.int64),
        "x_valid": rng.random(CAP) > 0.2,
        "d": rng.integers(0, 100, CAP).astype(np.int64),
        "m": rng.random(CAP) > 0.5,
    }


def _page(cols, num_rows):
    return Page((
        Column(jnp.asarray(cols["k"]), None, T.BIGINT, None),
        Column(jnp.asarray(cols["flag"]), jnp.asarray(cols["flag_valid"]),
               T.VARCHAR, Dictionary(FLAGS)),
        Column(jnp.asarray(cols["x"]), jnp.asarray(cols["x_valid"]),
               T.BIGINT, None),
        Column(jnp.asarray(cols["d"]), None, T.BIGINT, None),
        Column(jnp.asarray(cols["m"]), None, T.BOOLEAN, None),
    ), jnp.asarray(num_rows, dtype=jnp.int32))


# ---------------------------------------------------------------- the steps
# (key, builder, params) entries as the planner makes them. `below` and
# `at_least` are filters on d; `project` and `select` build fresh Pages.

def _below(limit):
    return (("filter", "d < ?"),
            lambda: lambda p, g: p.filter(p.column(D).values < g[0]),
            (jnp.int64(limit),))


def _at_least(limit):
    return (("filter", "d >= ?"),
            lambda: lambda p, g: p.filter(p.column(D).values >= g[0]),
            (jnp.int64(limit),))


def _project():
    def builder():
        def fn(p, g):
            x, d = p.column(X), p.column(D)
            x2 = Column(x.values * 2 + d.values, x.valid, T.BIGINT, None)
            return Page(p.columns + (x2,), p.num_rows)
        return fn
    return (("project", "x2 = x * 2 + d"), builder, ())


def _select():
    order = (K, FLAG, X, D, M)
    return (("select", order),
            lambda: lambda p, g: Page(tuple(p.columns[i] for i in order),
                                      p.num_rows), ())


def _np_steps(cols, num_rows, steps):
    """The rows the steps keep, and the projected columns, in NumPy."""
    live = np.arange(CAP) < num_rows
    for key, _, params in steps:
        if key == ("filter", "d < ?"):
            live &= cols["d"] < int(params[0])
        elif key == ("filter", "d >= ?"):
            live &= cols["d"] >= int(params[0])
    out = dict(cols)
    out["x2"] = cols["x"] * 2 + cols["d"]
    out["x2_valid"] = cols["x_valid"]
    return live, out


# ------------------------------------------------------------ the reference

_CHANNEL = {K: "k", FLAG: "flag", X: "x", D: "d", M: "m", X2: "x2"}


def _np_aggregate(cols, live, key_channels, specs):
    """{group key tuple: [one value per aggregate]} the SQL way: NULL keys
    make a group, aggregates skip NULL arguments, a sum/min/max/avg over
    no value is NULL, a count is 0."""
    def key_of(i):
        out = []
        for ch in key_channels:
            name = _CHANNEL[ch]
            if name == "flag":
                out.append(FLAGS[cols["flag"][i]]
                           if cols["flag_valid"][i] else None)
            else:
                out.append(int(cols[name][i]))
        return tuple(out)
    groups = {}
    for i in np.flatnonzero(live):
        groups.setdefault(key_of(i), []).append(i)
    if not key_channels:
        groups.setdefault((), [])
    result = {}
    for key, rows in groups.items():
        vals = []
        for spec in specs:
            picked = [i for i in rows
                      if spec.mask_channel is None or cols["m"][i]]
            if spec.input is None:
                vals.append(len(picked))
                continue
            name = _CHANNEL[spec.input]
            valid = cols.get(name + "_valid")
            args = [int(cols[name][i]) for i in picked
                    if valid is None or valid[i]]
            if spec.name == "count":
                vals.append(len(args))
            elif not args:
                vals.append(None)
            elif spec.name == "sum":
                vals.append(sum(args))
            elif spec.name == "min":
                vals.append(min(args))
            elif spec.name == "max":
                vals.append(max(args))
            elif spec.name == "avg":
                vals.append(sum(args) / len(args))
            elif spec.name == "approx_distinct":
                vals.append(len(set(args)))
            else:
                raise AssertionError(spec.name)
        result[key] = vals
    return result


def _rows(page, nkeys):
    """A result page as {key tuple: [aggregate values]}. (Not `to_host`:
    the direct path leaves the NULL key slot's code, one past the pool,
    in the NULL row's value lane.)"""
    assert page.selection is None
    n = int(page.num_rows)
    cols = []
    for c in page.columns:
        vals = np.asarray(c.values)[:n]
        valid = np.ones(n, dtype=bool) if c.valid is None \
            else np.asarray(c.valid)[:n]
        cols.append([None if not ok else
                     str(c.dictionary.values[v]) if c.dictionary is not None
                     else float(v) if vals.dtype.kind == "f" else int(v)
                     for v, ok in zip(vals, valid)])
    out = {}
    for i in range(n):
        key = tuple(c[i] for c in cols[:nkeys])
        assert key not in out, f"group {key} twice"
        out[key] = [c[i] for c in cols[nkeys:]]
    return out


# ---------------------------------------------------------------- the cases

def _spec(name, ch=None, mask=None):
    return AggSpec(name, ch, None if ch is None else T.BIGINT, mask)


# name -> (steps, key channels, aggregates, num_rows)
CASES = {
    # q6's shape: filter, project, global sums
    "global_q6_shape": (
        [_below(60), _project()], (), [_spec("sum", X2), _spec("count")],
        200),
    # q1's shape: dictionary keys take the direct path; NULL flags make a
    # group of their own in the key space's last slot
    "direct_dictionary_key_null_slot": (
        [_below(70), _project()], (FLAG,),
        [_spec("sum", X), _spec("avg", D), _spec("sum", X2),
         _spec("count")], 200),
    "sort_path_int64_key": (
        [_below(50)], (K,), [_spec("sum", X), _spec("count", X)], 200),
    "filter_where_aggregates_sorted": (
        [_below(80), _project()], (K,),
        [_spec("sum", X2, M), _spec("count", None, M), _spec("count")],
        200),
    "filter_where_aggregates_direct": (
        [_below(80)], (FLAG,),
        [_spec("sum", X, M), _spec("count", X, M), _spec("min", D, M)],
        200),
    "filter_where_aggregates_global": (
        [_below(80)], (), [_spec("sum", X, M), _spec("count", None, M)],
        200),
    "min_max_avg_count_direct": (
        [_below(65)], (FLAG,),
        [_spec("min", X), _spec("max", X), _spec("avg", X),
         _spec("count")], 200),
    "min_max_avg_count_sorted": (
        [_below(65)], (K,),
        [_spec("min", X), _spec("max", X), _spec("avg", X),
         _spec("count")], 200),
    "min_max_avg_count_global": (
        [_below(65)], (),
        [_spec("min", X), _spec("max", X), _spec("avg", X),
         _spec("count")], 200),
    "approx_distinct_global": (
        [_below(60)], (), [_spec("approx_distinct", X)], 200),
    "approx_distinct_grouped": (
        [_below(60)], (K,), [_spec("approx_distinct", X), _spec("count")],
        200),
    # d is never negative: the filter keeps no row
    "filter_empties_the_page_global": (
        [_below(0), _project()], (), [_spec("sum", X2), _spec("count")],
        200),
    "filter_empties_the_page_direct": (
        [_below(0)], (FLAG,), [_spec("sum", X), _spec("count")], 200),
    "filter_empties_the_page_sorted": (
        [_below(0)], (K,), [_spec("sum", X), _spec("count")], 200),
    "short_last_page": (
        [_below(90), _project()], (FLAG,),
        [_spec("sum", X2), _spec("count")], 37),
    "full_page": (
        [_below(40)], (K,), [_spec("sum", X), _spec("count")], CAP),
    "empty_input_page": (
        [_below(90)], (), [_spec("sum", X), _spec("count")], 0),
    "two_stacked_filters": (
        [_below(75), _at_least(20)], (K,),
        [_spec("sum", X), _spec("count")], 200),
    "filter_project_filter": (
        [_below(75), _project(), _at_least(20)], (FLAG,),
        [_spec("sum", X2), _spec("max", D), _spec("count")], 200),
    "project_builds_a_fresh_page": (
        [_below(55), _project(), _select()], (),
        [_spec("sum", X), _spec("count")], 200),
    "select_then_filter_sorted": (
        [_select(), _below(55), _at_least(5)], (K,),
        [_spec("min", D), _spec("max", D), _spec("count", X)], 200),
    # the direct path's masked reduce (PR 29) reads the selection through
    # its slot masks: q1's eight aggregates and fifteen states, min/max,
    # FILTER (WHERE) masks, a short and a full page
    "direct_q1_shape_eight_aggregates": (
        [_below(70), _project()], (FLAG,),
        [_spec("sum", X), _spec("sum", D), _spec("sum", X2),
         _spec("avg", X), _spec("avg", D), _spec("avg", X2),
         _spec("count", X), _spec("count")], 200),
    "direct_min_max_under_two_filters": (
        [_below(75), _at_least(20)], (FLAG,),
        [_spec("min", X), _spec("max", X), _spec("min", D),
         _spec("max", D), _spec("count")], 200),
    "direct_where_aggregates_short_page": (
        [_below(80), _project()], (FLAG,),
        [_spec("sum", X2, M), _spec("min", X2, M), _spec("max", X, M),
         _spec("count", None, M)], 37),
    "direct_full_page": (
        [_below(40)], (FLAG,),
        [_spec("sum", X), _spec("avg", X), _spec("count")], CAP),
}


def _final_op(nkeys, specs):
    state_channels, ch = [], nkeys
    for spec in specs:
        k = len(get_aggregate(spec.name, spec.input_type)
                .state(spec.input_type))
        state_channels.append(list(range(ch, ch + k)))
        ch += k
    return jax.jit(hash_aggregate(list(range(nkeys)), specs, Step.FINAL,
                                  state_channels))


def _single_step(steps, key_channels, specs, defer):
    """SINGLE-step aggregate over the steps' page, the filters deferred or
    compacting: what a mask-consuming single-step tail would see."""
    agg = hash_aggregate(key_channels, specs, Step.SINGLE)

    def run(page, groups):
        with defer_compaction(defer):
            for (_, builder, _), g in zip(steps, groups):
                page = builder()(page, g)
        if defer:
            assert page.selection is not None
        return agg(page)
    return jax.jit(run)


@pytest.mark.parametrize("name", list(CASES))
def test_deferred_chain_aggregates_the_rows_the_compacting_chain_does(name):
    steps, key_channels, specs, num_rows = CASES[name]
    cols = _columns()
    page = _page(cols, num_rows)
    nkeys = len(key_channels)
    groups = tuple(tuple(s[2]) for s in steps)
    if any(s.name == "approx_distinct" for s in specs):
        deferred = _single_step(steps, key_channels, specs, True)(
            page, groups)
        compacted = _single_step(steps, key_channels, specs, False)(
            page, groups)
    else:
        tail_key = ("agg-partial", key_channels, tuple(specs))
        chain_key = ("chain",) + tuple(s[0] for s in steps) + (tail_key,)
        assert chain_defers_compaction(chain_key)
        partial = hash_aggregate(key_channels, specs, Step.PARTIAL)
        final = _final_op(nkeys, specs)
        deferred_partial = compose_chain(steps, tail_key, lambda: partial)(
            page)
        assert deferred_partial.selection is None
        text = jit_cache._CACHE[chain_key][0].lower(page, groups).as_text(
            debug_info=True)
        # the filters move no row; what is compacted is the sorted
        # reduce's own states, a lane a group (PR 40: who moves where is
        # worked out once, under `group_bounds`, since PR 45)
        for tag in ("compact_gather", "compact_slots", "compact_shift"):
            assert text.count(tag) == sum(text.count(
                f"aggregate__{under}/aggregate__{tag}")
                for under in ("group_bounds", "segment_reduce")), tag
        assert ("aggregate__compact_shift" in text) \
            == (key_channels not in ((), (FLAG,)))   # the sorted path
        # a dictionary key's four slots reduce under slot masks (PR 29)
        assert ("aggregate__direct_masked_reduce" in text) \
            == (key_channels == (FLAG,))
        assert "aggregate__direct_segment_reduce" not in text
        deferred = final(deferred_partial)
        # the same steps with no tail: a plain chain, whose filters compact
        compact_page = compose_chain(steps)(page)
        assert compact_page.selection is None
        compacted = final(jax.jit(partial)(compact_page))

    got, forced = _rows(deferred, nkeys), _rows(compacted, nkeys)
    assert got == forced
    live, projected = _np_steps(cols, num_rows, steps)
    want = _np_aggregate(projected, live, key_channels, specs)
    assert set(got) == set(want)
    for key, vals in want.items():
        for spec, g, w in zip(specs, got[key], vals):
            if spec.name == "approx_distinct":
                assert abs(g - w) <= max(1, 0.05 * w), (key, g, w)
            elif isinstance(w, float):
                assert g == pytest.approx(w, rel=1e-12), (key, spec.name)
            else:
                assert g == w, (key, spec.name)


# ------------------------------------------------------------ the invariants

@pytest.mark.parametrize("key, defers", [
    (("chain", ("filter", 1), ("agg-partial", 2)), True),
    (("chain", ("filter", 1), ("project", 2), ("filter", 3),
      ("select", 4), ("agg-partial", 5)), True),
    (("chain", ("project", 1), ("agg-partial", 2)), True),
    (("chain", ("filter", 1)), False),
    (("chain", ("filter", 1), ("project", 2)), False),
    (("chain", ("filter", 1), ("agg-bypass", 2)), False),
    (("chain", ("filter", 1), ("topn-masked", 2)), False),
    (("chain", ("filter", 1), ("topn-masked", 2), ("agg-partial", 3)),
     False),
    (("chain", ("filter", 1), ("unnest", 2), ("agg-partial", 3)), False),
])
def test_only_lane_wise_chains_into_the_partial_aggregate_defer(key, defers):
    assert chain_defers_compaction(key) is defers


def _selected_page():
    page = _page(_columns(), 200)
    with defer_compaction():
        out = page.filter(page.column(D).values < 50)
    assert out.selection is not None and out.num_rows is page.num_rows
    return page, out


def test_a_deferred_filter_moves_no_row_and_ands_into_row_mask():
    page, out = _selected_page()
    for a, b in zip(page.columns, out.columns):
        assert a.values is b.values
    want = (np.arange(CAP) < 200) & (_columns()["d"] < 50)
    assert np.array_equal(np.asarray(out.row_mask()), want)
    # a second deferred filter ANDs; a compacting one honours the mask
    with defer_compaction():
        both = out.filter(out.column(D).values >= 10)
    assert np.array_equal(np.asarray(both.row_mask()),
                          want & (_columns()["d"] >= 10))
    compact = both.filter(jnp.ones(CAP, dtype=jnp.bool_))
    assert compact.selection is None
    assert int(compact.num_rows) == int((want & (_columns()["d"] >= 10))
                                        .sum())
    # column-wise views keep it; the scope leaves nothing behind
    assert out.select_columns([K, D]).selection is out.selection
    assert out.append_column(out.column(K)).selection is out.selection
    assert page.filter(page.column(D).values < 50).selection is None


@pytest.mark.parametrize("what", ["to_host", "to_pylist", "shrink_to",
                                  "pad_to", "gather", "concat_pages",
                                  "device_concat"])
def test_a_page_with_a_selection_is_refused_by_position_readers(what):
    from trino_tpu.page import concat_pages, device_concat
    page, out = _selected_page()
    call = {
        "to_host": lambda: out.to_host(),
        "to_pylist": lambda: out.to_pylist(),
        "shrink_to": lambda: out.shrink_to(128),
        "pad_to": lambda: out.pad_to(512),
        "gather": lambda: out.gather(jnp.arange(CAP), 200),
        "concat_pages": lambda: concat_pages([page, out]),
        "device_concat": lambda: device_concat([out, page]),
    }[what]
    with pytest.raises(ValueError, match="selection"):
        call()


def test_a_chain_never_returns_a_page_with_a_selection(monkeypatch):
    """The composer asserts it at trace time: a tail that hands the
    filtered page on (instead of aggregating it) cannot build."""
    # the failed build counts as an AOT fallback on the process ledger:
    # put the ledger back for the tests that read it
    monkeypatch.setitem(jit_cache._STATS, "aot_fallbacks",
                        jit_cache._STATS["aot_fallbacks"])
    page = _page(_columns(), 200)
    leak = compose_chain([_below(50)], ("agg-partial", "leaks"),
                         lambda: lambda p: p)
    with pytest.raises(AssertionError):
        leak(page)
    # the same steps into a real partial aggregate, and with no tail
    ok = compose_chain([_below(50), _project()], ("agg-partial", "ok"),
                       lambda: hash_aggregate((), [_spec("count")],
                                              Step.PARTIAL))
    assert ok(page).selection is None
    assert compose_chain([_below(50), _project()])(page).selection is None


def test_a_plain_chain_lowers_to_what_the_steps_alone_lower_to():
    """Nothing of the mechanism reaches a chain that does not defer: its
    program is the steps composed by hand, instruction for instruction."""
    page = _page(_columns(), 200)
    steps = [_below(50), _project(), _at_least(5)]
    groups = tuple(tuple(s[2]) for s in steps)
    compose_chain(steps)
    key = ("chain",) + tuple(s[0] for s in steps)
    chain = jit_cache._CACHE[key][0].lower(page, groups).as_text()

    def by_hand(p, gs):
        for (_, builder, _), g in zip(steps, gs):
            p = builder()(p, g)
        return p
    hand = jax.jit(by_hand).lower(page, groups).as_text()
    name = jit_cache.program_name(key)
    assert chain.replace(f"jit_{name}", "F") \
        == hand.replace("jit_by_hand", "F")


def test_operator_stats_cost_the_deferred_chain_in_its_own_mode():
    """`profiler.chain_weights` rebuilds the chain through `chain_steps`:
    a deferred chain's filter step costs no gather (its weight is the
    predicate's alone, well under the compacting filter's)."""
    from trino_tpu.exec.local_planner import chain_steps
    from trino_tpu.obs import profiler
    page = _page(_columns(), 200)
    steps = [_below(50), _project()]
    groups = tuple(tuple(s[2]) for s in steps)
    tail_key = ("agg-partial", (), "weights")

    def partial():
        return hash_aggregate((), [_spec("sum", X2)], Step.PARTIAL)
    deferred_key = ("chain",) + tuple(s[0] for s in steps) + (tail_key,)
    plain_key = deferred_key[:-1]
    deferred = profiler.chain_weights(
        deferred_key, lambda: chain_steps(deferred_key, steps, partial),
        page, groups)
    plain = profiler.chain_weights(
        plain_key, lambda: chain_steps(plain_key, steps), page, groups)
    assert len(deferred) == 3 and len(plain) == 2
    assert deferred[0] < 0.5 * plain[0], (deferred, plain)


# ------------------------------------------------- the counters, as served

@pytest.fixture(scope="module")
def served():
    import json
    import urllib.request

    from trino_tpu.exec import LocalQueryRunner
    from trino_tpu.server import TrinoServer
    srv = TrinoServer(LocalQueryRunner.tpch("tiny"),
                      result_cache=False).start()

    def get(uri):
        with urllib.request.urlopen(uri) as resp:
            return json.loads(resp.read())

    def run(sql):
        req = urllib.request.Request(
            f"{srv.base_uri}/v1/statement", data=sql.encode(),
            method="POST")
        req.add_header("X-Trino-User", "test")
        with urllib.request.urlopen(req) as resp:
            payload = json.loads(resp.read())
        qid, rows = payload["id"], []
        while True:
            rows += payload.get("data", [])
            if "nextUri" not in payload:
                break
            payload = get(payload["nextUri"])
        assert payload["stats"]["state"] == "FINISHED", payload
        return rows, get(f"{srv.base_uri}/v1/query/{qid}")["stats"]
    yield run
    srv.stop()


@pytest.mark.parametrize("shape", ["q6", "q1", "q3"])
def test_served_queries_count_their_compactions(served, shape):
    import chip_smoke
    sql = {"q6": chip_smoke.Q6.format(date="1994-01-01", disc="0.06",
                                      qty=24),
           "q1": chip_smoke.Q1, "q3": chip_smoke.Q3}[shape]
    rows, stats = served(sql)
    assert rows
    if shape == "q3":
        # its scans feed joins (plain chains), its aggregate chain has
        # no filter step
        assert stats["compactions_run"] >= 1
        assert stats["compactions_deferred"] == 0
    else:
        assert stats["compactions_deferred"] >= 1
        assert stats["compactions_run"] == 0


def test_served_q3_reports_its_probe_compactions(served):
    """`GET /v1/query/<id>` carries the five probe-path counters (PR 31)
    beside `compactions_run`; q3's two joins each hand their probe
    buffers to `_compact_counted`."""
    import chip_smoke
    _rows, stats = served(chip_smoke.Q3)
    ran = stats["probe_compactions_tight"] + stats["probe_compactions_full"]
    assert ran + stats["probe_compactions_skipped"] >= 2
    assert stats["probe_compactions_tight"] >= 1
    assert 0 < stats["probe_compaction_lanes_gathered"] \
        < stats["probe_compaction_lanes_in"]
    assert stats["compactions_run"] >= 1


def _scatter_operands(text):
    """[[dims of each operand]] of every scatter in a StableHLO module."""
    import re
    found = re.findall(r'"stablehlo\.scatter"\(.*?\) : \((.*?)\) ->', text,
                       flags=re.S)
    return [[tuple(int(d) for d in re.findall(r"(\d+)x", t))
             for t in re.findall(r"tensor<([^>]*)>", sig)] for sig in found]


def test_q1s_chain_reduces_its_slot_table_under_masks(served):
    """q1's aggregating chain at `tiny`, lowered again from the signature
    it was dispatched with: the direct GROUP BY runs under the masked
    scope and no scatter touches an operand as long as the page — what is
    left are `compact()`'s, over the twelve slots."""
    import chip_smoke
    served(chip_smoke.Q1)
    def dispatched_with(entry):
        sig = next(iter(entry[2]))      # the AOT executables' signatures
        return sig[0].unflatten([
            leaf() if isinstance(leaf, type)    # a Python scalar operand
            else jax.ShapeDtypeStruct(leaf[1], np.dtype(leaf[0]))
            for leaf in sig[1:]])
    # this file's own cases made chains of the same name, 256 lanes wide
    fn, args = next(
        (e[0], dispatched_with(e)) for k, e in jit_cache._CACHE.items()
        if jit_cache.program_name(k)
        == "aggregate__chain_filter_project_agg_partial" and e[2]
        and len(k[-1][1]) == 2      # q6's has no key, q1's two
        and dispatched_with(e)[0].capacity >= 4096)
    text = fn.lower(*args).as_text(debug_info=True)
    assert "aggregate__agg_partial/aggregate__direct_masked_reduce" in text
    assert "aggregate__direct_segment_reduce" not in text
    scatters = _scatter_operands(text)
    assert scatters, "compact()'s scatters over the slots stay"
    assert all(max(dims, default=0) <= 12
               for op in scatters for dims in op), scatters


@pytest.mark.parametrize("shape", ["q6", "q1", "q3"])
def test_served_queries_count_their_direct_reduces(served, shape):
    """`direct_reduces_masked` counts one per dispatch of q1's aggregating
    chain (the chain has a filter, so that is `compactions_deferred`);
    q6's GROUP BY is global and q3's sorted: neither counts."""
    import chip_smoke
    sql = {"q6": chip_smoke.Q6.format(date="1994-01-01", disc="0.06",
                                      qty=24),
           "q1": chip_smoke.Q1, "q3": chip_smoke.Q3}[shape]
    _, stats = served(sql)
    assert stats["direct_reduces_scattered"] == 0
    assert stats["direct_reduces_masked"] == (
        stats["compactions_deferred"] if shape == "q1" else 0)


@pytest.mark.parametrize("limit, form, where", [
    (24, "masked", "l_quantity < 30"), (23, "scattered", "30 > l_quantity")])
def test_the_crossover_decides_the_form_and_the_counter_says_which(
        monkeypatch, limit, form, where):
    """Twelve slots x two states sit at the crossover or one past it: the
    same rows either way, counted under the form that ran. (Literals are
    operands, so each case and the sorted run spell the predicate their
    own way: a program is traced once per shape.)"""
    from trino_tpu.exec import LocalQueryRunner
    from trino_tpu.ops import aggregate
    monkeypatch.setattr(aggregate, "_MASKED_MAX_SLOT_STATES", limit)
    sql = ("SELECT l_returnflag, l_linestatus, sum(l_quantity) FROM lineitem"
           " WHERE {} GROUP BY 1, 2 ORDER BY 1, 2")
    runner = LocalQueryRunner.tpch("tiny")
    got = runner.execute(sql.format(where)).rows
    stats = runner.last_query_stats
    other = {"masked": "scattered", "scattered": "masked"}[form]
    assert stats[f"direct_reduces_{form}"] == stats["compactions_deferred"] \
        >= 1
    assert stats[f"direct_reduces_{other}"] == 0
    monkeypatch.setattr(aggregate, "_DIRECT_MAX_GROUPS", 0)
    assert got == runner.execute(
        sql.format("NOT (l_quantity >= 30)")).rows
    assert runner.last_query_stats[f"direct_reduces_{form}"] == 0
    assert len(got) == 4
