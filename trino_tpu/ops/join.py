"""Hash join as sorted-build + binary-search probe + cumsum expansion.

Reference parity: operator/join/ (HashBuilderOperator.java:59, PagesHash.java,
LookupJoinOperator.java:36, HashSemiJoinOperator, NestedLoopJoinOperator).

TPU design: open-addressing tables probe with data-dependent loops — a poor
VPU fit. Instead:
  build:  sort build rows by join key (lax.sort)                O(n log n)
  probe:  lower/upper bound via vectorized searchsorted         O(m log n)
  expand: match counts -> cumsum offsets -> one gather per side O(out)
This is exact for duplicate keys (a probe row emits hi-lo rows) and fully
static-shape: the output page has a planner-chosen capacity; the operator also
returns the true match total so the executor can detect overflow and re-run
at a larger capacity bucket (SURVEY §7 hard part 1).

Composite keys collapse to one u64 via a mixing hash and every join type
verifies the real key columns post-expansion (scope `join__composite_verify`;
the executor names such a join's programs `join__join_composite`,
`join__uprobe_composite`, `join__join_prep_composite`): INNER/LEFT/FULL filter
collision slots exactly (LEFT/FULL additionally rescue probe rows whose
every candidate was a collision as null-extension rows), and SEMI/ANTI/MARK
re-check candidates and scatter the verdict back per probe row. SQL
semantics: NULL join keys never match (including NULL = NULL); LEFT/FULL
rows without matches emit once with the other side NULL.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.ops.radix import sort_by_keys
from trino_tpu.page import Column, Page, op_scope


class JoinType:
    INNER = "inner"
    LEFT = "left"          # probe side preserved
    SEMI = "semi"          # probe rows with >=1 match (IN / EXISTS)
    ANTI = "anti"          # probe rows with 0 matches (NOT IN w/o nulls)
    FULL = "full"          # both sides preserved (executor accumulates the
                           # build-matched mask and emits unmatched build
                           # rows via unmatched_build_page)
    MARK = "mark"          # all probe rows + bool match channel
    # (HashSemiJoinOperator appends the semi-join result as a column;
    # used when the match symbol escapes into projections/other filters)


_MIX = np.uint64(0x9E3779B97F4A7C15)


def _mix64(x: jnp.ndarray) -> jnp.ndarray:
    """splitmix64 finalizer — the PagesHash hash-combining analog."""
    x = x.astype(jnp.uint64)
    x = (x ^ (x >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> 27)) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> 31)


def _key_u64(page: Page, channels: Sequence[int]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(key, key_is_null): single u64 key; composite keys mix-hashed."""
    cols = [page.column(ch) for ch in channels]
    null = jnp.zeros(page.capacity, dtype=jnp.bool_)
    for c in cols:
        if c.valid is not None:
            null = null | ~c.valid
    def to_u64(raw):
        if raw.dtype == jnp.bool_:
            return raw.astype(jnp.uint64)
        if jnp.issubdtype(raw.dtype, jnp.floating):
            # canonicalize -0.0 -> +0.0 so SQL-equal doubles get equal bits
            return jax.lax.bitcast_convert_type(
                raw.astype(jnp.float64) + 0.0, jnp.uint64)
        return raw.astype(jnp.uint64)

    if len(cols) == 1:
        return to_u64(cols[0].values), null
    acc = jnp.zeros(page.capacity, dtype=jnp.uint64)
    for c in cols:
        k = to_u64(c.values)
        acc = _mix64(acc ^ _mix64(k) ^ (acc * _MIX))
    return acc, null


def _mark_page(probe: Page, matched: jnp.ndarray, pnull: jnp.ndarray,
               n_build_rows: jnp.ndarray,
               build_has_null: jnp.ndarray) -> Page:
    """Append the semi-join verdict as a boolean channel.

    Full IN-subquery 3VL: TRUE on a key match; NULL when the probe key is
    NULL against a non-empty build side, OR when there is no match but the
    build side contains a NULL key; FALSE otherwise (incl. any probe against
    an empty build side)."""
    value = matched & ~pnull
    definite = jnp.where(pnull, n_build_rows == 0, ~build_has_null)
    valid = matched | definite
    mark = Column(value, valid, T.BOOLEAN, None)
    return Page(tuple(probe.columns) + (mark,), probe.num_rows)


def _semi_verdict(probe: Page, join_type: str, null_aware: bool,
                  matched: jnp.ndarray, pnull: jnp.ndarray,
                  p_dead: jnp.ndarray, n_build_rows: jnp.ndarray,
                  build_has_null: jnp.ndarray) -> Tuple[Page, jnp.ndarray]:
    """A SEMI, ANTI or MARK join's answer from `matched` — which probe
    lanes found their key on the build side — however that was found
    (a sorted build's run counts in hash_join, a slot of the set table
    in set_semi_join): the one place the NULL rules live (`null_aware`,
    see hash_join). Returns (output_page, its rows)."""
    if join_type == JoinType.MARK:
        if null_aware:
            out = _mark_page(probe, matched, pnull, n_build_rows,
                             build_has_null)
        else:
            mark = Column(matched & ~pnull, None, T.BOOLEAN, None)
            out = Page(tuple(probe.columns) + (mark,), probe.num_rows)
        return out, probe.num_rows.astype(jnp.int64)
    if join_type == JoinType.SEMI:
        keep = matched & ~p_dead
    elif null_aware:
        # NOT IN: non-null probe keeps iff unmatched AND build has no
        # NULLs; NULL probe keeps only against an empty build
        keep = probe.row_mask() & jnp.where(
            pnull, n_build_rows == 0, ~matched & ~build_has_null)
    else:
        # NOT EXISTS: unmatched live rows keep (NULL keys never match)
        keep = probe.row_mask() & ~matched
    out = probe.filter(keep)
    return out, out.num_rows.astype(jnp.int64)


def _live_key_bounds(bkey: jnp.ndarray, live_key: jnp.ndarray):
    """(kmin, kmax) of the live non-NULL build keys in u64 space — kmin >
    kmax where there is none: what the executor's router reads
    (exec/local_planner._prepare_probe)."""
    kmin = jnp.min(jnp.where(live_key, bkey,
                             jnp.uint64(0xFFFFFFFFFFFFFFFF)))
    kmax = jnp.max(jnp.where(live_key, bkey, jnp.uint64(0)))
    return kmin, kmax


def prepare_build(build_keys: Sequence[int], semi: bool = False):
    """Build-phase kernel: sort the build side ONCE into a LookupSource-like
    pytree consumed by every probe-page call (reference:
    operator/join/LookupSourceFactory — the build runs once per join, not
    once per probe page). Returns prep(build_page) -> prepared tuple.
    `semi`: the build of a semi, anti or mark join, whose phases a trace
    reads as `join__semi_build`, not as an inner join's."""
    build_keys = tuple(build_keys)
    sort_scope = "join__semi_build" if semi else "join__build_sort"
    runs_scope = "join__semi_build" if semi else "join__build_runs"

    def prep(build: Page):
        with op_scope(sort_scope):
            bkey, bnull = _key_u64(build, build_keys)
            # dead/null build rows: mask their key to u64::MAX and sort by
            # (key, dead) — keeps the key array globally sorted for
            # searchsorted while live rows occupy the prefix [0, n_live)
            b_dead = ~build.row_mask() | bnull
            u64max = jnp.uint64(0xFFFFFFFFFFFFFFFF)
            bkey_masked = jnp.where(b_dead, u64max, bkey)
            (bkey_s, b_dead_s), bperm = sort_by_keys([bkey_masked, b_dead])
        with op_scope(runs_scope):
            n_live_build = jnp.sum(~b_dead_s).astype(jnp.int32)
            live_b = build.row_mask()
            n_build_rows = jnp.sum(live_b).astype(jnp.int32)
            build_has_null = jnp.any(bnull & live_b)
            # per-position run length of equal keys: lets the probe derive its
            # upper bound from the lower bound (hi = lo + run_len[lo]) with no
            # second searchsorted — each probe-side searchsorted costs a full
            # sort-engine pass at scale
            n = build.capacity
            idx = jnp.arange(n, dtype=jnp.int32)
            boundary = (bkey_s != jnp.roll(bkey_s, 1)).at[0].set(True)
            run_start = jax.lax.cummax(jnp.where(boundary, idx, 0))
            nxt = jnp.where(boundary, idx, n)
            suffix_min = jnp.flip(jax.lax.cummin(jnp.flip(nxt)))
            next_start = jnp.concatenate(
                [suffix_min[1:], jnp.full((1,), n, dtype=suffix_min.dtype)])
            run_len = (next_start - run_start).astype(jnp.int32)
            # max duplicate-key run among LIVE build rows: 1 means the build
            # side is unique (a primary/dimension key) and probes can take the
            # no-expansion fast path (unique_inner_probe) — the executor
            # fetches this once per join
            max_run_live = jnp.max(jnp.where(jnp.arange(n, dtype=jnp.int32)
                                             < n_live_build, run_len, 0))
            # live-key min/max (u64 space): the executor fetches these with
            # max_run and, when the span is small (dense surrogate keys — every
            # TPC-H/DS key), builds a direct-address lookup table
            # (build_dense_table) so a probe lane costs ONE gather instead
            # of a sort-engine searchsorted pass and three gathers: the
            # table holds the build row itself for a unique INNER build,
            # the sorted position (for run_len) for every other consumer
            kmin, kmax = _live_key_bounds(bkey, ~b_dead)
        return (build, bkey_s, bperm, n_live_build, n_build_rows,
                build_has_null, run_len, max_run_live, kmin, kmax)
    return prep


_DENSE_SENTINEL = np.int32(0x7FFFFFFF)


def build_dense_table(size: int, semi: bool = False):
    """Direct-address lookup table for a sorted build, one int32 slot per
    key of the live span: op(bkey_s, n_live, kmin, payload=None) ->
    table[key - kmin], sentinel INT32_MAX where no live key falls. Dead
    positions and out-of-span keys route to the dropped slot `size`.

    `payload` is what a slot holds, indexed by sorted position:
      None   — the position of the key's FIRST sorted occurrence, so that
               run_len[pos] still yields the duplicate count: what
               hash_join reads (duplicates, LEFT/FULL, SEMI/ANTI/MARK);
      bperm  — the ORIGINAL build row of that (unique) key: what the
               unique INNER probe reads, in memory (unique_inner_probe)
               or spilled (spilled_dense_probe, which then needs no
               sorted keys and no permutation on the device: 4 B a slot
               instead of 12 B a row).

    The TPU analog of the reference's array-based lookup source for dense
    bigint keys (operator/join/... ArrayBasedLookupSource idea): one
    scatter at build time buys gather-only probes. Every TPC-H/DS join key
    is a dense surrogate (orderkey/partkey/.._sk), so this path carries
    the hot joins; sparse/hashed keys fall back to searchsorted.
    `semi`: a semi, anti or mark join's, read as `join__semi_build`."""
    scope = "join__semi_build" if semi else "join__build_dense_table"

    def op(bkey_s, n_live, kmin, payload=None):
        with op_scope(scope):
            n = bkey_s.shape[0]
            idx = jnp.arange(n, dtype=jnp.int32)
            raw = (bkey_s - kmin).astype(jnp.int64)
            oob = (idx >= n_live) | (raw < 0) | (raw >= size)
            slot = jnp.where(oob, size, raw)
            return jnp.full(size, _DENSE_SENTINEL, jnp.int32) \
                .at[slot].min(idx if payload is None else payload,
                              mode="drop")
    return op


def _dense_lo(table: jnp.ndarray, kmin, pkey: jnp.ndarray) -> jnp.ndarray:
    """table[pkey - kmin] in ONE gather, or the sentinel (>= any n_live,
    never a build row) for a key outside the table's span. The range test
    runs on the u64 difference — a key below kmin wraps past any size —
    and the index is narrowed to int32 before the gather: the TPU has no
    64-bit lanes, and a table has under 2^31 slots."""
    raw = pkey - kmin
    inb = raw < jnp.uint64(table.shape[0])
    slot = jnp.where(inb, raw, 0).astype(jnp.int32)
    return jnp.where(inb, jnp.take(table, slot, mode="clip"),
                     _DENSE_SENTINEL)


def _dense_row_lookup(table: jnp.ndarray, kmin, pkey: jnp.ndarray,
                      p_dead: jnp.ndarray):
    """The lookup of a UNIQUE build through its row table
    (build_dense_table with bperm as payload): one gather a probe lane,
    no second one through the sort permutation — a position matters only
    for run_len, and a unique build has no runs. Returns (brow, found):
    the build row as int32 (the sentinel where the slot is empty) and the
    match mask; slot identity is key equality, NULL and dead lanes never
    match."""
    brow = _dense_lo(table, kmin, pkey)
    return brow, (brow != _DENSE_SENTINEL) & ~p_dead


# the lookup of a semi, anti or mark join reads as its own in a trace
_PROBE_SCOPE = {JoinType.SEMI: "join__semi_probe",
                JoinType.ANTI: "join__semi_probe",
                JoinType.MARK: "join__mark_probe"}


def semi_build_stats(build_keys: Sequence[int]):
    """What a semi, anti or mark join must know of its build side before
    a lane is ordered: op(build_page) -> (kmin, kmax, n_build_rows,
    build_has_null) — the live non-NULL keys' bounds in u64 space (as
    prepare_build forms them: kmin > kmax where there is none), the live
    rows, and whether a live key is NULL. One pass of reductions over the
    page as it was collected, no sort: the executor routes on the span
    (exec/local_planner._prepare_probe), the NULL rules read the rest."""
    build_keys = tuple(build_keys)

    def op(build: Page):
        with op_scope("join__semi_build"):
            bkey, bnull = _key_u64(build, build_keys)
            live_b = build.row_mask()
            kmin, kmax = _live_key_bounds(bkey, live_b & ~bnull)
            n_build_rows = jnp.sum(live_b).astype(jnp.int32)
            build_has_null = jnp.any(bnull & live_b)
        return kmin, kmax, n_build_rows, build_has_null
    return op


def build_set_table(build_keys: Sequence[int], size: int):
    """Membership table of a single-column build, straight from the keys
    as they arrive: op(build_page, kmin) -> (table, key_cols) where
    table[key - kmin] is 0 where a live non-NULL key falls and the
    sentinel elsewhere. A semi, anti or mark join asks whether a key is
    there, not where — no sorted position, no permutation, no run length
    — so ONE scatter of a constant over the unsorted lanes is the whole
    build; duplicates write the same value. Dead lanes, NULL keys and
    keys outside the span route to the dropped slot `size`, as
    build_dense_table routes them. `key_cols` are the key columns cut to
    no lane: their dictionaries are all the probe still asks of the
    build page, whose columns the executor then frees."""
    build_keys = tuple(build_keys)

    def op(build: Page, kmin):
        with op_scope("join__semi_build"):
            bkey, bnull = _key_u64(build, build_keys)
            raw = bkey - kmin
            oob = ~build.row_mask() | bnull | (raw >= jnp.uint64(size))
            slot = jnp.where(oob, jnp.uint64(size), raw).astype(jnp.int32)
            table = jnp.full(size, _DENSE_SENTINEL, jnp.int32) \
                .at[slot].set(np.int32(0), mode="drop")
        key_cols = tuple(
            Column(c.values[:0], None, c.type, c.dictionary)
            for c in (build.column(bk) for bk in build_keys))
        return table, key_cols
    return op


def set_semi_join(probe_keys: Sequence[int], join_type: str,
                  null_aware: bool = True
                  ) -> Callable[[Page, tuple], Tuple[Page, jnp.ndarray]]:
    """A SEMI, ANTI or MARK join on ONE key column against the set table:
    op(probe_page, (table, kmin, n_build_rows, build_has_null, key_cols))
    -> (output_page, its rows), what hash_join(prepared=True) returns for
    the same join over a sorted build. One gather a probe lane — slot
    identity is key equality, to_u64 being injective — then the verdict
    hash_join's single-key exit runs (_semi_verdict). The output never
    exceeds the probe page, so nothing overflows."""
    probe_keys = tuple(probe_keys)
    if len(probe_keys) != 1 or join_type not in _PROBE_SCOPE:
        raise ValueError("the set table serves SEMI, ANTI and MARK joins "
                         "on one key column")

    def op(probe: Page, prepared) -> Tuple[Page, jnp.ndarray]:
        table, kmin, n_build_rows, build_has_null, key_cols = prepared
        _check_key_dictionaries(probe, probe_keys, key_cols)
        with op_scope(_PROBE_SCOPE[join_type]):
            pkey, pnull = _key_u64(probe, probe_keys)
            p_dead = ~probe.row_mask() | pnull
            matched = (_dense_lo(table, kmin, pkey) != _DENSE_SENTINEL) \
                & ~p_dead
        return _semi_verdict(probe, join_type, null_aware, matched, pnull,
                             p_dead, n_build_rows, build_has_null)
    return op


def _outer_fill(outer: bool):
    """The scope of what makes a LEFT or FULL join outer — an unmatched
    probe row's one emitted slot, the slots that are null-extensions, the
    build columns' validity there — so a trace reads it as its own; an
    INNER join's ops stay where they were."""
    return op_scope("join__outer_fill") if outer \
        else contextlib.nullcontext()


def _check_key_dictionaries(probe: Page, probe_keys: Sequence[int],
                            build_key_cols: Sequence[Column]) -> None:
    """Dictionary-coded keys join by code, so both sides must share one
    pool: content-fingerprint inequality (page.py round 10), not object
    identity — pools with byte-identical values share one code mapping,
    so joining across them is exact."""
    for pk, bc in zip(probe_keys, build_key_cols):
        pd = probe.column(pk).dictionary
        bd = bc.dictionary
        if pd is not None and bd is not None and pd != bd:
            raise NotImplementedError(
                "string join keys across distinct dictionaries; "
                "re-encode to a shared dictionary first")


def _check_lookup(lookup: str) -> None:
    if lookup not in ("search", "dense"):
        raise ValueError(
            f"join lookup must be 'search' or 'dense', not {lookup!r}")


def hash_join(
    probe_keys: Sequence[int],
    build_keys: Sequence[int],
    join_type: str = JoinType.INNER,
    output_capacity: Optional[int] = None,
    verify_composite: bool = True,
    prepared: bool = False,
    null_aware: bool = True,
    lookup: str = "search",
    probe_out: Optional[Sequence[int]] = None,
    build_out: Optional[Sequence[int]] = None,
) -> Callable[[Page, Page], Tuple[Page, jnp.ndarray]]:
    """Build op(probe_page, build) -> (output_page, true_total_rows).

    `build` is a build Page, or (with prepared=True) the tuple produced by
    prepare_build — the executor sorts the build once and probes many pages.
    Output layout: probe columns ++ build columns (semi/anti: probe only).
    output_capacity: static result capacity; defaults to probe capacity.
    true_total_rows may exceed num_rows when the capacity was too small —
    the executor re-plans at a larger bucket (never silently truncates).

    `lookup` picks the probe strategy (exec/local_planner._prepare_probe
    routes by the build's live key span): 'search' = sort-engine
    searchsorted, 'dense' = one gather against the direct-address table
    of sorted POSITIONS (prepared[10], build_dense_table with no
    payload), then run_len at the position — this kernel serves
    duplicate builds and the LEFT/FULL/SEMI/ANTI/MARK kinds, which need
    the run; a unique INNER build probes its table of build ROWS through
    unique_inner_probe instead. The mesh shard_map bodies prep inline
    (prepared=False), have no table and probe by 'search'.

    null_aware governs SEMI/ANTI/MARK null semantics (reference:
    sql/planner/QueryPlanner IN-predicate planning vs correlated-EXISTS
    decorrelation):
      True  — IN-subquery 3VL: a NULL probe key or a NULL in a non-empty
              build side makes the membership UNKNOWN, so ANTI keeps a
              non-matching row only when the build side is null-free, and a
              NULL probe key survives ANTI only against an EMPTY build
              (x NOT IN (empty) is TRUE even for NULL x).
      False — EXISTS semantics: NULL correlation keys simply never match
              (the correlated equality evaluates to NULL -> no inner row
              qualifies), so ANTI keeps every unmatched live probe row
              including NULL-key rows, and build-side NULLs are irrelevant.
    """
    probe_keys = tuple(probe_keys)
    build_keys = tuple(build_keys)
    composite = len(probe_keys) > 1
    outer = join_type in (JoinType.LEFT, JoinType.FULL)
    _check_lookup(lookup)

    def op(probe: Page, build) -> Tuple[Page, jnp.ndarray]:
        aux_table = None
        if prepared:
            if lookup == "dense":
                aux_table = build[10]
            (build, bkey_s, bperm, n_live_build, n_build_rows,
             build_has_null, run_len, _max_run, kmin, _kmax) = build[:10]
        else:
            (build, bkey_s, bperm, n_live_build, n_build_rows,
             build_has_null, run_len, _max_run, kmin, _kmax) = \
                prepare_build(build_keys)(build)
        n_build = build.capacity
        n_probe = probe.capacity
        n_probe_cols = probe.num_columns
        cap = output_capacity or n_probe
        _check_key_dictionaries(
            probe, probe_keys, [build.column(bk) for bk in build_keys])

        with op_scope(_PROBE_SCOPE.get(join_type, "join__probe_lookup")):
            pkey, pnull = _key_u64(probe, probe_keys)

            p_dead = ~probe.row_mask() | pnull
            n_build_m1 = jnp.maximum(n_build - 1, 0)
            if lookup == "dense" and aux_table is not None:
                # dense surrogate keys: ONE gather against the direct-address
                # table (slot identity implies key equality — no verify gather)
                lo = _dense_lo(aux_table, kmin, pkey)
                lo_c = jnp.minimum(lo, n_build_m1)
                found = lo < n_live_build
            else:
                # ONE searchsorted over the live prefix (method="sort" routes
                # the lookup through the TPU sort engine — 20x faster at
                # millions of keys than the default per-level binary-search
                # gathers: 0.14 s against 2.8 s for 6.3M probes on a v5e, PR
                # 23 — at a minute more of compile time)
                lo = jnp.searchsorted(bkey_s, pkey, side="left",
                                      method="sort").astype(jnp.int32)
                lo_c = jnp.minimum(lo, n_build_m1)
                found = (jnp.take(bkey_s, lo_c, mode="clip") == pkey) & \
                    (lo < n_live_build)
            # the upper bound comes from the build side's precomputed run
            # lengths
            hi = lo + jnp.where(found,
                                jnp.take(run_len, lo_c, mode="clip"), 0)
            lo = jnp.minimum(lo, n_live_build)
            hi = jnp.minimum(hi, n_live_build)
            counts = jnp.where(p_dead, 0, hi - lo).astype(jnp.int64)

        def verdict(matched: jnp.ndarray) -> Tuple[Page, jnp.ndarray]:
            return _semi_verdict(probe, join_type, null_aware, matched,
                                 pnull, p_dead, n_build_rows,
                                 build_has_null)

        if join_type in (JoinType.SEMI, JoinType.ANTI, JoinType.MARK) \
                and not (composite and verify_composite):
            # single-column keys: to_u64 is injective, hash match == key match
            return verdict(counts > 0)

        with op_scope("join__probe_expand"):
            emit = counts
            if outer:
                # unmatched live probe rows (incl. null keys) emit one
                # null-extended row
                with _outer_fill(True):
                    live_probe = probe.row_mask()
                    emit = jnp.where(live_probe & (counts == 0), 1, counts)
                    emit = jnp.where(live_probe, emit, 0)
            offsets = jnp.cumsum(emit)
            total = offsets[-1]
            starts = offsets - emit  # exclusive prefix

            out_idx = jnp.arange(cap, dtype=jnp.int64)
            # which probe row produced output slot j: last start <= j
            prow = jnp.searchsorted(offsets, out_idx, side="right",
                                    method="sort").astype(jnp.int32)
            prow_c = jnp.minimum(prow, n_probe - 1)
            j_within = out_idx - jnp.take(starts, prow_c, mode="clip")
            brow_sorted = jnp.take(lo, prow_c, mode="clip") + j_within
            brow = jnp.take(bperm, jnp.minimum(brow_sorted, n_build - 1),
                            mode="clip").astype(jnp.int32)
            slot_live = out_idx < jnp.minimum(total, cap)
            matched = jnp.take(counts, prow_c, mode="clip") > 0

        if join_type in (JoinType.SEMI, JoinType.ANTI, JoinType.MARK):
            # composite keys: re-check real key equality on each expanded
            # candidate, then scatter-or back to probe rows. Exact whenever the
            # hash-expansion fits in cap (else total > cap -> executor re-runs
            # at a bigger bucket, same contract as INNER).
            with op_scope("join__composite_verify"):
                keep = slot_live & matched
                for pk, bk in zip(probe_keys, build_keys):
                    pv = jnp.take(probe.column(pk).values, prow_c,
                                  mode="clip")
                    bv = jnp.take(build.column(bk).values, brow,
                                  mode="clip")
                    keep = keep & (pv == bv)
                verified = jnp.zeros(n_probe, dtype=jnp.bool_) \
                    .at[prow_c].max(keep, mode="drop")
            out, rows = verdict(verified)
            return out, jnp.where(total <= cap, rows, total)

        real_match = slot_live & matched      # slot is a real hash candidate
        with _outer_fill(outer):
            build_is_null = slot_live & ~matched  # null-extension rows

        # composite keys: re-check real key equality per candidate slot so
        # hash collisions are filtered exactly (single-key u64 is injective)
        keep = jnp.ones(cap, dtype=jnp.bool_)
        if composite and verify_composite:
            with op_scope("join__composite_verify"):
                for pk, bk in zip(probe_keys, build_keys):
                    pv = jnp.take(probe.column(pk).values, prow_c,
                                  mode="clip")
                    bv = jnp.take(build.column(bk).values, brow,
                                  mode="clip")
                    keep = keep & (pv == bv)
        verified_slot = real_match & keep

        if outer and composite and verify_composite:
            # a probe row whose EVERY candidate was a hash collision must
            # still emit one null-extended row: rescue its first candidate
            # slot as the null-extension carrier
            verified_any = jnp.zeros(n_probe, dtype=jnp.bool_) \
                .at[prow_c].max(verified_slot, mode="drop")
            rescue = real_match & (j_within == 0) & \
                ~jnp.take(verified_any, prow_c, mode="clip")
            build_is_null = build_is_null | rescue
            keep = keep | rescue

        # PruneJoinColumns: gather only emitted channels (the probe/build
        # gathers at output capacity are the kernel's dominant cost)
        with op_scope("join__output_gather"):
            p_idx = range(probe.num_columns) if probe_out is None else probe_out
            b_idx = range(build.num_columns) if build_out is None else build_out
            pcols = tuple(probe.columns[i].gather(prow_c) for i in p_idx)
            bcols = []
            for i in b_idx:
                c = build.columns[i]
                g = c.gather(brow)
                with _outer_fill(outer):
                    valid = g.valid_mask() & ~build_is_null
                bcols.append(Column(g.values, valid, c.type, c.dictionary))
            out_rows = jnp.minimum(total, cap).astype(jnp.int32)
            out_page = Page(pcols + tuple(bcols), out_rows)

        if composite and verify_composite:
            # drop collision slots (null-extension slots pass: matched=False
            # there so keep was never narrowed for them... they start True)
            with op_scope("join__composite_verify"):
                keep_final = jnp.where(real_match, keep, True)
                out_page = out_page.filter(keep_final)
            # overflow contract: if every hash match fit in cap, the filtered
            # count is the exact total; else keep the (over)count so the
            # executor re-plans at a larger capacity
            total = jnp.where(total <= cap,
                              out_page.num_rows.astype(jnp.int64), total)

        if join_type == JoinType.FULL:
            # which build rows found >=1 verified probe match (accumulated by
            # the executor across probe pages; unmatched rows emit at end)
            build_matched = jnp.zeros(n_build, dtype=jnp.bool_) \
                .at[brow].max(verified_slot, mode="drop")
            return out_page, total, build_matched
        return out_page, total

    return op


def prepare_build_spilled(build_keys: Sequence[int]):
    """Spilling build phase (HashBuilderOperator.java:163 spill state
    machine, re-thought for HBM): device memory holds ONLY what probing
    needs — the sorted u64 key array and the sort permutation — while the
    build's payload columns move to host RAM (the executor fetches them
    once and frees the device page). Probing then runs entirely against
    the key array; matched rows' build columns are gathered HOST-side at
    match count (attach_build_host), so a 150M-row build costs ~12 bytes/
    row of HBM instead of the full page + run-length structures.

    Returns op(build_page) -> (bkey_s, bperm, n_live, n_build_rows,
    build_has_null, is_unique)."""
    build_keys = tuple(build_keys)

    def prep(build: Page):
        with op_scope("join__build_sort"):
            bkey, bnull = _key_u64(build, build_keys)
            b_dead = ~build.row_mask() | bnull
            u64max = jnp.uint64(0xFFFFFFFFFFFFFFFF)
            bkey_masked = jnp.where(b_dead, u64max, bkey)
            bkey_s, b_dead_s, bperm = jax.lax.sort(
                [bkey_masked, b_dead,
                 jnp.arange(build.capacity, dtype=jnp.int32)], num_keys=2)
        n_live = jnp.sum(~b_dead_s).astype(jnp.int32)
        live_b = build.row_mask()
        n_build_rows = jnp.sum(live_b).astype(jnp.int32)
        build_has_null = jnp.any(bnull & live_b)
        idx = jnp.arange(build.capacity, dtype=jnp.int32)
        dup = (bkey_s[1:] == bkey_s[:-1]) & (idx[1:] < n_live)
        is_unique = ~jnp.any(dup)
        kmin, kmax = _live_key_bounds(bkey, ~b_dead)
        return (bkey_s, bperm, n_live, n_build_rows, build_has_null,
                is_unique, kmin, kmax)
    return prep


def spilled_dense_probe(probe_keys: Sequence[int],
                        probe_out: Optional[Sequence[int]] = None):
    """Probe a spilled build through its dense row table — the lookup of
    unique_inner_probe(lookup='dense') with the table alone on the
    device: one gather per probe row. Returns (pre_page, found_mask,
    match_count) — compaction is deferred to the executor, which skips it
    entirely when every live probe row matched (the common
    fact-to-dimension case)."""
    probe_keys = tuple(probe_keys)

    def op(probe: Page, table, kmin):
        with op_scope("join__probe_lookup"):
            pkey, pnull = _key_u64(probe, probe_keys)
            brow, found = _dense_row_lookup(
                table, kmin, pkey, ~probe.row_mask() | pnull)
            brow = jnp.where(found, brow, 0)
        return _pre_page(probe, probe_out, brow), found, \
            jnp.sum(found).astype(jnp.int64)

    return op


def _pre_page(probe: Page, probe_out: Optional[Sequence[int]],
              brow: jnp.ndarray) -> Page:
    """The unique probes' output: the emitted probe channels ++ the build
    row of each lane (`brow`), at PROBE order, nothing moved yet."""
    p_idx = range(probe.num_columns) if probe_out is None else probe_out
    typ = T.INTEGER if brow.dtype == jnp.int32 else T.BIGINT
    return Page(tuple(probe.columns[i] for i in p_idx)
                + (Column(brow, None, typ, None),), probe.num_rows)


_ANCHOR_LOG2 = 10


def _searchsorted_anchored(bkey_s: jnp.ndarray, pkey: jnp.ndarray
                           ) -> jnp.ndarray:
    """side='left' searchsorted for HUGE sorted arrays: method='sort'
    co-sorts the whole build array with every probe batch (a ~5GB
    workspace per call against a 150M-key build — the SF100 OOM), so
    instead (1) one sort-method search against a 1/2^10 anchor subsample,
    then (2) 2^10-window lower_bound via ~11 branchless gather rounds.
    Workspace is O(probe + build/1024); gathers run at probe size."""
    n = bkey_s.shape[0]
    stride = 1 << _ANCHOR_LOG2
    anchors = bkey_s[::stride]
    coarse = jnp.searchsorted(anchors, pkey, side="left", method="sort")
    pos = (jnp.maximum(coarse, 1) - 1) * stride
    # invariant: bkey_s[pos-1] < key (anchor strictly below); advance in
    # halving steps while the probe stays below the key
    step = stride
    while step > 0:
        nxt = pos + step
        v = jnp.take(bkey_s, jnp.minimum(nxt - 1, n - 1), mode="clip")
        advance = (nxt <= n) & (v < pkey)
        pos = jnp.where(advance, nxt, pos)
        step //= 2
    return pos


def spilled_unique_probe(probe_keys: Sequence[int],
                         probe_out: Optional[Sequence[int]] = None):
    """Probe phase against a spilled build: identical to unique_inner_probe
    but consuming only (bkey_s, bperm, n_live) — no build Page on device.
    Composite-key verification happens host-side in attach_build_host
    (the build columns live there). Returns (pre, found, count); the
    executor compacts by the fetched count as it does behind
    unique_inner_probe."""
    probe_keys = tuple(probe_keys)

    def op(probe: Page, bkey_s, bperm, n_live):
        with op_scope("join__probe_lookup"):
            n_build = bkey_s.shape[0]
            pkey, pnull = _key_u64(probe, probe_keys)
            p_dead = ~probe.row_mask() | pnull
            lo = _searchsorted_anchored(bkey_s, pkey)
            lo_c = jnp.minimum(lo, jnp.maximum(n_build - 1, 0))
            found = (jnp.take(bkey_s, lo_c, mode="clip") == pkey) & \
                (lo < n_live) & ~p_dead
            brow = jnp.take(bperm, lo_c, mode="clip").astype(jnp.int64)
        return _pre_page(probe, probe_out, brow), found, \
            jnp.sum(found).astype(jnp.int64)

    return op


def attach_build_host(pre: Page, n_probe_cols: int, host_cols,
                      verify: Optional[Sequence[Tuple[int, int]]] = None,
                      emit: Optional[Sequence[int]] = None) -> Page:
    """Host-side attach for the spilled path: gather build columns from
    host numpy arrays at the matched rows' original indices and stage only
    the match-count-sized result. `host_cols` is [(values_np, valid_np or
    None, type, dictionary)]. `verify` = [(probe_ch, build_col_idx)] pairs
    re-checked for composite keys (hash collisions). `emit` selects which
    host_cols are emitted (default all) — verify-only key columns need not
    be staged back to device."""
    import numpy as np
    n = int(pre.num_rows)
    brow = np.asarray(
        jax.device_get(pre.columns[n_probe_cols].values[:max(n, 1)]))[:n] \
        .astype(np.int64)
    keep = None
    if verify:
        for pch, bci in verify:
            pv = np.asarray(jax.device_get(
                pre.columns[pch].values[:max(n, 1)]))[:n]
            bv = host_cols[bci][0][brow]
            eq = pv == bv
            keep = eq if keep is None else (keep & eq)
    if keep is not None and not keep.all():
        sel = np.nonzero(keep)[0]
        brow = brow[sel]
    else:
        sel = None
    cap = pre.capacity
    bcols = []
    emit_cols = host_cols if emit is None else [host_cols[i] for i in emit]
    for values, valid, typ, d in emit_cols:
        g = values[brow]
        v = valid[brow] if valid is not None else None
        bcols.append(Column.from_numpy(
            _pad_np(g, cap), typ,
            valid=None if v is None else _pad_np(v, cap), dictionary=d))
    pcols = pre.columns[:n_probe_cols]
    if sel is not None:
        keep_dev = jnp.zeros(cap, dtype=jnp.bool_) \
            .at[jnp.asarray(sel)].set(True)
        filtered = Page(pcols, pre.num_rows).filter(keep_dev)
        pcols = filtered.columns
        nrows = filtered.num_rows
    else:
        nrows = pre.num_rows
    return Page(tuple(pcols) + tuple(bcols), nrows)


def _pad_np(arr, cap):
    import numpy as np
    if len(arr) == cap:
        return arr
    out = np.zeros(cap, dtype=arr.dtype)
    out[:len(arr)] = arr
    return out


def unique_inner_probe(
    probe_keys: Sequence[int],
    build_keys: Sequence[int],
    verify_composite: bool = True,
    lookup: str = "search",
    probe_out: Optional[Sequence[int]] = None,
) -> Callable[[Page, tuple], Tuple[Page, jnp.ndarray, jnp.ndarray]]:
    """INNER-join probe against a UNIQUE build side (max key run == 1) —
    the dimension/primary-key case covering every TPC-H/DS fact-to-dim
    join. No cumsum expansion, no output-slot searchsorted, no
    capacity-sized gathers (round-4 profiling: those cost ~0.7s per
    MILLION probe rows in the general kernel). With lookup='dense' the
    searchsorted, the key gather that verifies it and the gather through
    the sort permutation collapse to ONE gather a probe lane: prepared[10]
    is then the table of build ROWS (build_dense_table with bperm as
    payload; exec/local_planner._prepare_probe builds it for this
    consumer and no other), the lookup spilled_dense_probe shares.

    Returns (pre_page, found_mask, match_count): pre_page is probe columns
    ++ a `brow` channel (INTEGER from the table, BIGINT from the search)
    at PROBE order, nothing moved yet. The
    executor fetches the count and then compacts in a second program
    (exec/local_planner._compact_counted): not at all when every live row
    matched (count == num_rows; the common fact-to-dim case), the matched
    prefix alone at the count's pow2 capacity when the buffer is more than
    twice that, else one full-capacity filter — then runs attach_build at
    live size. Output can never overflow (<= probe rows), so no capacity
    re-run loop is needed."""
    probe_keys = tuple(probe_keys)
    build_keys = tuple(build_keys)
    composite = len(probe_keys) > 1
    _check_lookup(lookup)

    def op(probe: Page, prepared):
        (build, bkey_s, bperm, n_live_build, n_build_rows,
         build_has_null, run_len, _max_run, kmin, _kmax) = prepared[:10]
        n_build = build.capacity
        _check_key_dictionaries(
            probe, probe_keys, [build.column(bk) for bk in build_keys])
        with op_scope("join__probe_lookup"):
            pkey, pnull = _key_u64(probe, probe_keys)
            p_dead = ~probe.row_mask() | pnull
            if lookup == "dense":
                brow, found = _dense_row_lookup(prepared[10], kmin, pkey,
                                                p_dead)
            else:
                lo = jnp.searchsorted(bkey_s, pkey, side="left", method="sort")
                lo_c = jnp.minimum(lo, jnp.maximum(n_build - 1, 0))
                found = (jnp.take(bkey_s, lo_c, mode="clip") == pkey) & \
                    (lo < n_live_build) & ~p_dead
                brow = jnp.take(bperm, lo_c, mode="clip").astype(jnp.int64)
            if composite and verify_composite:
                # unique build: at most one candidate — verify it directly
                with op_scope("join__composite_verify"):
                    for pk, bk in zip(probe_keys, build_keys):
                        bv = jnp.take(build.column(bk).values, brow,
                                      mode="clip")
                        found = found & (probe.column(pk).values == bv)
            brow = jnp.where(found, brow, 0)
        return _pre_page(probe, probe_out, brow), found, \
            jnp.sum(found).astype(jnp.int64)

    return op


def build_key_bounds(build_keys: Sequence[int]):
    """Dynamic-filter source (operator/DynamicFilterSourceOperator.java +
    server/DynamicFilterService.java:102 analog, collapsed to the
    single-controller design): after the build side is collected, its key
    min/max become device scalars the probe-side scan stream filters by —
    no coordinator round trip, the scalars never leave the device.

    Exact-set pruning (Trino's small-build IN-list filter) is deliberately
    NOT a separate pass here: the unique-build probe path already drops
    non-matching probe rows before any build-column gather — its lookup
    yields the match mask and count, and the executor compacts the matched
    prefix at the count's own capacity — which is the same work an
    exact-set semi prefilter would do."""
    build_keys = tuple(build_keys)

    def op(build: Page):
        c = build.column(build_keys[0])
        live = build.row_mask()
        if c.valid is not None:
            live = live & c.valid
        v = c.values
        if jnp.issubdtype(v.dtype, jnp.integer):
            big, small = jnp.iinfo(v.dtype).max, jnp.iinfo(v.dtype).min
        else:
            big, small = jnp.inf, -jnp.inf
        lo = jnp.min(jnp.where(live, v, big))
        hi = jnp.max(jnp.where(live, v, small))
        return lo, hi

    return op


def range_prefilter(probe_key: int):
    """Probe-side dynamic-filter application, measured before anything
    moves: the mask of live rows whose key can be in [lo, hi] (NULL keys
    never match an INNER join, so they drop too) and its count — one
    lane-wise program, no gather. The executor fetches the count with the
    window's others and compacts only where the filter turned out to be
    worth keeping (exec/local_planner._coalesce_stream)."""

    def op(page: Page, lo, hi):
        with op_scope("join__range_mask"):
            c = page.column(probe_key)
            keep = (c.values >= lo) & (c.values <= hi) & page.row_mask()
            if c.valid is not None:
                keep = keep & c.valid
            return keep, jnp.sum(keep).astype(jnp.int32)

    return op


def attach_build(n_probe_cols: int,
                 build_out: Optional[Sequence[int]] = None
                 ) -> Callable[[Page, tuple], Page]:
    """Second phase of the unique-build fast path: gather build columns
    (only the emitted channels) at the compacted (live-size) brow indices
    and restore the probe++build output layout."""

    def op(pre: Page, prepared) -> Page:
        with op_scope("join__output_gather"):
            build = prepared[0]
            brow = pre.columns[n_probe_cols].values.astype(jnp.int32)
            live = pre.row_mask()
            brow = jnp.where(live, brow, 0)
            b_idx = range(build.num_columns) if build_out is None else build_out
            bcols = tuple(build.columns[i].gather(brow) for i in b_idx)
        return Page(tuple(pre.columns[:n_probe_cols]) + bcols, pre.num_rows)

    return op


def unmatched_build_page(probe_meta: Sequence[Tuple[T.Type, object]],
                         ) -> Callable[[Page, jnp.ndarray], Page]:
    """FULL-join finisher (operator/join/LookupOuterOperator.java analog):
    emit build rows never matched by any probe page, null-extended on the
    probe side. `matched` is the OR of per-page build_matched masks;
    `probe_meta` is (type, dictionary) per probe column so null columns keep
    the stream's dictionaries (concat/union safety downstream)."""
    probe_meta = tuple(probe_meta)

    def op(build: Page, matched: jnp.ndarray) -> Page:
        kept = build.filter(~matched & build.row_mask())
        cap = kept.capacity
        pcols = tuple(
            Column(jnp.zeros(cap, dtype=t.dtype),
                   jnp.zeros(cap, dtype=jnp.bool_), t, d)
            for t, d in probe_meta)
        return Page(pcols + tuple(kept.columns), kept.num_rows)

    return op
