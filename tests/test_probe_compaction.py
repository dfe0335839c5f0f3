"""The kept-prefix compaction (PR 31): `Page.compact_to(mask, capacity)` is
`Page.filter(mask).shrink_to(capacity)` lane for lane — the same stable
partition, every column gathered through the head of the permutation only —
for a host driver that holds the kept count before it compacts (the join's
probe path, exec/local_planner._compact_counted; its driver tests are in
test_join_shapes.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.page import Column, Dictionary, Page, defer_compaction

CAP = 16384
ROWS = 16000          # the lanes behind it hold data too: only masks drop them
LIST_LEN = 3
POOL = np.array(["AIR", "MAIL", "RAIL", "SHIP", "TRUCK"], dtype=object)
D12_2 = T.DecimalType(12, 2)


def _page(seed=31):
    """One column of every layout a probe page can carry: int64, decimal,
    nullable, dictionary string (nullable too), ARRAY (`lengths`) and MAP
    (`lengths` and `aux`, the value plane a string one)."""
    rng = np.random.default_rng(seed)

    def ints(hi, shape=(CAP,), dtype=np.int64):
        return jnp.asarray(rng.integers(0, hi, shape).astype(dtype))
    lengths = ints(LIST_LEN + 1, dtype=np.int32)
    return Page((
        Column(ints(1 << 40), None, T.BIGINT, None),
        Column(ints(10 ** 9), None, D12_2, None),
        Column(ints(1000), jnp.asarray(rng.random(CAP) > 0.2), T.BIGINT,
               None),
        Column(ints(len(POOL), dtype=np.int32),
               jnp.asarray(rng.random(CAP) > 0.1), T.VARCHAR,
               Dictionary(POOL)),
        Column(ints(500, (CAP, LIST_LEN)), None, T.ArrayType(T.BIGINT),
               None, lengths),
        Column(ints(50, (CAP, LIST_LEN)), jnp.asarray(rng.random(CAP) > 0.3),
               T.MapType(T.BIGINT, T.VARCHAR), None, lengths,
               ints(len(POOL), (CAP, LIST_LEN), np.int32),
               Dictionary(POOL)),
    ), jnp.asarray(ROWS, dtype=jnp.int32))


def _mask(kind):
    u = np.random.default_rng(7).random(CAP)
    return jnp.asarray({"none": u < 0, "all": u >= 0, "sparse": u < 0.01,
                        "quarter": u < 0.25, "dense": u < 0.6}[kind])


def _rungs(kind):
    """Every pow2 capacity from the kept count's own (the planner's rungs
    start at 1024) up to the page's."""
    count = int(np.asarray(_mask(kind))[:ROWS].sum())
    rung = 1024
    while rung < count:
        rung *= 2
    out = []
    while rung <= CAP:
        out.append(rung)
        rung *= 2
    return out


CASES = [pytest.param(kind, cap, id=f"{kind}-{cap}")
         for kind in ("none", "all", "sparse", "quarter", "dense")
         for cap in _rungs(kind)]


def _arrays(col):
    return [a for a in (col.values, col.valid, col.lengths, col.aux)
            if a is not None]


@pytest.mark.parametrize("kind, cap", CASES)
def test_compact_to_is_filter_then_shrink_column_for_column(kind, cap):
    page, mask = _page(), _mask(kind)
    want = jax.jit(lambda p, m: p.filter(m))(page, mask).shrink_to(cap)
    got = jax.jit(lambda p, m: p.compact_to(m, cap))(page, mask)
    count = int(np.asarray(mask)[:ROWS].sum())
    assert int(got.num_rows) == int(want.num_rows) == count <= cap
    assert got.capacity == cap and got.selection is None
    assert len(got.columns) == len(page.columns)
    for g, w, src in zip(got.columns, want.columns, page.columns):
        assert g.type == w.type == src.type
        assert g.dictionary is src.dictionary
        assert g.aux_dictionary is src.aux_dictionary
        assert len(_arrays(g)) == len(_arrays(w)) == len(_arrays(src))
        for ga, wa in zip(_arrays(g), _arrays(w)):
            assert ga.shape == wa.shape and ga.dtype == wa.dtype
            # the contract: the kept prefix, in order (what lies behind
            # num_rows is each form's own leftovers)
            np.testing.assert_array_equal(np.asarray(ga)[:count],
                                          np.asarray(wa)[:count])
    # and the kept rows are the input's, in input order
    keep = np.flatnonzero(np.asarray(mask)[:ROWS])
    np.testing.assert_array_equal(
        np.asarray(got.columns[0].values)[:count],
        np.asarray(page.columns[0].values)[keep])


def test_compact_to_refuses_a_selection_and_a_larger_capacity():
    page = _page()
    with defer_compaction():
        selected = page.filter(_mask("dense"))
    assert selected.selection is not None
    with pytest.raises(ValueError, match="selection"):
        selected.compact_to(_mask("sparse"), 1024)
    with pytest.raises(ValueError, match="grow"):
        page.compact_to(_mask("sparse"), 2 * CAP)


def test_compact_to_never_defers():
    """It is the host's second step: its page leaves the program, so the
    chain composer's deferral does not reach it."""
    page, mask = _page(), _mask("sparse")
    with defer_compaction():
        out = page.compact_to(mask, 1024)
    assert out.selection is None and out.capacity == 1024
    assert int(out.num_rows) == int(np.asarray(mask)[:ROWS].sum())


def test_tight_program_keeps_the_old_scopes_and_gathers_at_the_rung():
    """Under the probe compaction's program name the two phases are
    `join__compact_slots` / `join__compact_gather`, as `filter`'s were
    until PR 35, so a device trace reads the new form under the old names;
    and no gather of the program is as long as the page."""
    import re

    from trino_tpu.exec import jit_cache
    key = ("probe-compact", 1024)
    assert jit_cache.program_name(key) == "join__probe_compact"
    program = jit_cache.named(lambda p, m: p.compact_to(m, 1024), key)
    text = jax.jit(program).lower(_page(), _mask("sparse")).as_text(
        debug_info=True)
    assert "jit(join__probe_compact)/join__compact_slots" in text
    assert "jit(join__probe_compact)/join__compact_gather" in text
    sizes = [int(n) for n in re.findall(
        r'"stablehlo\.gather"\(.*?\) -> tensor<(\d+)x', text, flags=re.S)]
    assert sizes and set(sizes) == {1024}, sizes


def test_full_program_shifts_and_gathers_nothing():
    """The full-capacity case of `_compact_counted` is `Page.filter` under
    the same program name: `join__compact_slots`, then the shift-and-select
    rounds as `join__compact_shift` (PR 35) — no index, so no gather, no
    scatter and no sort."""
    from trino_tpu.exec import jit_cache
    key = ("probe-compact",)
    assert jit_cache.program_name(key) == "join__probe_compact"
    program = jit_cache.named(lambda p, m: p.filter(m), key)
    text = jax.jit(program).lower(_page(), _mask("dense")).as_text(
        debug_info=True)
    assert "jit(join__probe_compact)/join__compact_slots" in text
    assert "jit(join__probe_compact)/join__compact_shift" in text
    for op in ("compact_gather", "stablehlo.gather", "stablehlo.scatter",
               "stablehlo.sort"):
        assert op not in text, op
