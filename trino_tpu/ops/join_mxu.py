"""MXU-native join kernels: density-partitioned indicator matmuls.

"Density-optimized Intersection-free Mapping and Matrix Multiplication
for Join-Project Operations" (arXiv 2206.04995) lowers join+project,
semijoin, and distinct-project onto blocked matmuls over 0/1
key-indicator matrices: give every key of a dense range its own matrix
column (the intersection-free mapping — slot identity IS key equality,
nothing to re-verify), partition the range into MXU-aligned column
blocks, and let the matrix unit brute-force the lookups the gather path
serves with memory-bound sort-engine / gather passes. JSPIM
(arXiv 2508.08503) motivates routing between the strategies by observed
density and skew — the router lives in
exec/local_planner._prepare_probe and reads the CBO estimates stamped
by planner/optimizer.annotate_adaptive_hints.

Two kernel families:

  matmul_lookup — per probe row, (match count, first sorted build
    position) against the build side's per-key [count, pos] table: one
    (rows x BLOCK) @ (BLOCK x 2) `jnp.dot` per key-range block. The
    result feeds hash_join's existing cumsum-expansion machinery, so
    INNER join-project, semijoin, anti-semijoin and mark probes execute
    as matmul kernels with outputs byte-identical to the gather path.

  aggregate tables (scatter_agg_table + blocked_lookup) — the
    many-to-many aggregating join (TPC-DS q64/q72 shapes). The paper's
    M = A·Bᵀ match multiplicities feed SUM/COUNT directly: the build
    side scatters to per-key [pair count, Σw, #valid w] vectors, each
    probe row matmul-looks-up its key's vector, and the join never
    materializes the cross product — a probe row carries its pair
    multiplicity instead of expanding `count` times through the
    capacity-laddered gather kernels.

Accumulation dtypes (the low-precision-accumulate-safe choice): lookup
matmuls multiply one-hot rows against values bounded by the build row
count, so f32 accumulation is EXACT while every operand stays under
2^24 — the router gates builds at 16M rows. Aggregate tables carry
value sums: f64 on CPU (exact for int64/short-decimal sums < 2^53),
f32 on TPU where f32 is the MXU's native accumulate and the engine's
doubles are approximate anyway.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from trino_tpu.page import shared_scope

# MXU-aligned key-range block width (the 128x128 systolic array tiles
# 512-wide operands without padding waste; CPU Eigen likes it too)
BLOCK = 512

# f32 one-hot lookups are exact only while counts/positions fit the
# mantissa: the router refuses builds at or past this row count
MAX_EXACT_ROWS = 1 << 24

# Accumulation of integer/short-decimal build sums is exact only while
# every per-key total stays inside the accumulation dtype's mantissa:
# 2^53 for f64 (CPU), 2^24 for f32 (TPU/GPU). scatter_agg_table checks
# the built table against the bound for ITS dtype and the router falls
# back to the gather join's exact int64 arithmetic past it.
MAX_EXACT_INT_SUM = float(1 << 53)


def exact_int_sum_bound(dtype) -> float:
    return MAX_EXACT_INT_SUM if dtype == jnp.float64 \
        else float(1 << 24)


def accum_dtype():
    """Aggregate-table accumulation dtype per platform (see module
    docstring): f64 on CPU, f32 on TPU/GPU."""
    return jnp.float64 if jax.default_backend() == "cpu" else jnp.float32


def distinct_live_keys(bkey_s: jnp.ndarray,
                       n_live: jnp.ndarray) -> jnp.ndarray:
    """Distinct key count over the sorted live prefix — the numerator of
    the router's observed density (distinct keys / key span)."""
    n = bkey_s.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    boundary = (bkey_s != jnp.roll(bkey_s, 1)).at[0].set(True)
    return jnp.sum(boundary & (idx < n_live)).astype(jnp.int32)


def build_count_pos_table(slots: int):
    """Build-side per-key [match count, first sorted position] table over
    the dense key range [kmin, kmin + slots): the columns of the
    indicator matrix, materialized as the (slots x 2) right-hand matmul
    operand. Dead/out-of-span keys route to a dropped slot. Returns
    op(bkey_s, n_live, kmin) -> f32 (slots, 2)."""

    def op(bkey_s, n_live, kmin):
        with shared_scope("mxu_table_build", "join"):
            return build(bkey_s, n_live, kmin)

    def build(bkey_s, n_live, kmin):
        n = bkey_s.shape[0]
        idx = jnp.arange(n, dtype=jnp.int32)
        live = idx < n_live
        raw = (bkey_s - kmin).astype(jnp.int64)
        oob = ~live | (raw < 0) | (raw >= slots)
        slot = jnp.where(oob, slots, raw)
        cnt = jnp.zeros(slots + 1, dtype=jnp.float32) \
            .at[slot].add(jnp.where(oob, 0.0, 1.0))
        pos = jnp.full(slots + 1, float(n), dtype=jnp.float32) \
            .at[slot].min(idx.astype(jnp.float32))
        return jnp.stack([cnt[:slots], pos[:slots]], axis=1)

    return op


def blocked_lookup(table: jnp.ndarray, kmin, pkey: jnp.ndarray,
                   block: int = BLOCK) -> jnp.ndarray:
    """The core MXU kernel: per-row one-hot lookup of `table[key - kmin]`
    as a sequence of (rows x block) @ (block x C) matmuls over key-range
    blocks. Out-of-span keys produce all-zero rows (no match — exactly
    the intersection-free contract). Accumulates in the table's dtype."""
    slots, ncols = table.shape
    dtype = table.dtype
    raw = (pkey - kmin).astype(jnp.int64)
    inb = (raw >= 0) & (raw < slots)
    off = jnp.where(inb, raw, -1).astype(jnp.int32)
    n = pkey.shape[0]
    step = min(block, slots)
    nblocks = -(-slots // step)
    # ragged last block: zero rows no in-span key can select
    table = jnp.pad(table, ((0, nblocks * step - slots), (0, 0)))
    lane = jnp.arange(step, dtype=jnp.int32)

    # a LOOP over the key-range blocks, not an unrolled chain: unrolled,
    # the blocks are independent and a scheduler may keep every
    # (rows x block) indicator alive at once — at 65 536 slots that is
    # 128 of them per shard, which is what killed the 8-device mesh
    # program under JAX 0.9 (tens of GB on the CPU backend)
    def one_block(i, acc):
        start = i * step
        onehot = (off[:, None] == (start + lane)[None, :]).astype(dtype)
        rows = jax.lax.dynamic_slice_in_dim(table, start, step, axis=0)
        return acc + jnp.dot(onehot, rows, preferred_element_type=dtype)

    with shared_scope("mxu_lookup", "join"):
        return jax.lax.fori_loop(0, nblocks, one_block,
                                 jnp.zeros((n, ncols), dtype=dtype))


def matmul_lookup(table: jnp.ndarray, kmin, pkey: jnp.ndarray,
                  block: int = BLOCK
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(count, first sorted position) per probe key via blocked indicator
    matmuls — the MXU replacement for the dense-gather / searchsorted
    probe. Absent keys: count 0 (position is meaningless there; callers
    mask on count)."""
    looked = blocked_lookup(table, kmin, pkey, block=block)
    return (looked[:, 0].astype(jnp.int32),
            looked[:, 1].astype(jnp.int32))


def scatter_agg_table(slots: int, vec_specs, key_channel: int,
                      dtype=None):
    """Build-side accumulation table for the aggregating join: one
    scatter-add per vector over the dense key range. `vec_specs` is a
    tuple of ('cnt',) | ('sum', channel, 'i'|'f') |
    ('validcnt', channel) — the per-key pair count, Σ of a build column
    over live rows (nulls add 0), and the per-key count of non-null
    values of a build column.
    Returns op(build_page, kmin) -> (table (slots x C), distinct_keys,
    mag_ok): distinct feeds the router's density check, and mag_ok is
    False when any INTEGER-kind per-key sum reached the accumulation
    dtype's exact-integer bound (2^53 for f64, 2^24 for f32), so the
    router must fall back to the gather join's exact int64 arithmetic
    (float-kind sums are excluded: f64 is the engine's double
    arithmetic anyway)."""
    from trino_tpu.ops.join import _key_u64
    vec_specs = tuple(vec_specs)

    def op(build, kmin):
        with shared_scope("mxu_table_build", "join"):
            return scatter(build, kmin)

    def scatter(build, kmin):
        dt = accum_dtype() if dtype is None else dtype
        bkey, bnull = _key_u64(build, (key_channel,))
        live = build.row_mask() & ~bnull
        raw = (bkey - kmin).astype(jnp.int64)
        oob = ~live | (raw < 0) | (raw >= slots)
        slot = jnp.where(oob, slots, raw)
        cols = []
        for spec in vec_specs:
            if spec[0] == "cnt":
                vec = jnp.where(oob, 0.0, 1.0)
            else:
                c = build.column(spec[1])
                valid = c.valid_mask() & ~oob
                if spec[0] == "validcnt":
                    vec = jnp.where(valid, 1.0, 0.0)
                else:
                    vec = jnp.where(valid, c.values.astype(dt), 0)
            cols.append(jnp.zeros(slots + 1, dtype=dt)
                        .at[slot].add(vec.astype(dt))[:slots])
        table = jnp.stack(cols, axis=1)
        cnt_idx = vec_specs.index(("cnt",))
        distinct = jnp.sum(table[:, cnt_idx] > 0).astype(jnp.int32)
        mag_ok = jnp.bool_(True)
        bound = exact_int_sum_bound(dt)
        for i, spec in enumerate(vec_specs):
            if spec[0] == "sum" and spec[2] == "i":
                mag_ok = mag_ok & (jnp.max(jnp.abs(table[:, i]))
                                   < bound)
        return table, distinct, mag_ok

    return op


def key_bounds(channel: int):
    """Live-key min/max in u64 key space for one channel — the fused
    aggregating join's span probe (kmin > kmax signals an all-dead
    build). Returns op(page) -> (kmin, kmax)."""
    from trino_tpu.ops.join import _key_u64

    def op(page):
        key, null = _key_u64(page, (channel,))
        live = page.row_mask() & ~null
        u64max = jnp.uint64(0xFFFFFFFFFFFFFFFF)
        kmin = jnp.min(jnp.where(live, key, u64max))
        kmax = jnp.max(jnp.where(live, key, jnp.uint64(0)))
        return kmin, kmax

    return op


def agg_join_lookup(key_channel: int, group_channels, derive, helpers,
                    block: int = BLOCK):
    """Per-probe-page derived rows for the fused aggregating join: group
    columns pass through, each aggregate becomes a per-row contribution
    built from the row's matmul-looked-up per-key build vector (its pair
    multiplicity / Σw / #valid-w), and rows with no match (or dead /
    null-key rows) filter out — the page that feeds the standard SINGLE
    aggregation is at most probe-sized, never the cross product.

    `derive` entries (one per aggregate, planner-encoded):
      ('pairs',)                 count(*)  -> pair multiplicity
      ('cntp', probe_ch)         count(p.v) -> multiplicity where v valid
      ('sump', probe_ch, 'i'|'f') sum(p.v) -> v * multiplicity (NULL
                                  rides the probe column's validity)
      ('cntb', vec_idx)          count(b.w) -> looked-up #valid-w
      ('sumb', vec_idx, 'i'|'f', helper_pos) sum(b.w) -> looked-up Σw
    `helpers` lists the #valid-w vector indices that must ride along as
    extra summed columns (the post kernel turns them into SUM null
    masks). Returns op(page, table, kmin) -> Page."""
    from trino_tpu import types as T
    from trino_tpu.ops.join import _key_u64
    from trino_tpu.page import Column, Page
    group_channels = tuple(group_channels)
    derive = tuple(derive)
    helpers = tuple(helpers)

    def op(page, table, kmin):
        pkey, pnull = _key_u64(page, (key_channel,))
        looked = blocked_lookup(table, kmin, pkey, block=block)
        cnt = looked[:, 0]
        cnt_i = cnt.astype(jnp.int64)
        live = page.row_mask() & ~pnull & (cnt > 0)
        cols = [page.columns[ch] for ch in group_channels]
        for d in derive:
            if d[0] == "pairs":
                cols.append(Column(cnt_i, None, T.BIGINT, None))
            elif d[0] == "cntp":
                c = page.column(d[1])
                cols.append(Column(jnp.where(c.valid_mask(), cnt_i, 0),
                                   None, T.BIGINT, None))
            elif d[0] == "sump":
                c = page.column(d[1])
                if d[2] == "f":
                    vals = c.values.astype(jnp.float64) * \
                        cnt.astype(jnp.float64)
                    typ = T.DOUBLE
                else:
                    vals = c.values.astype(jnp.int64) * cnt_i
                    typ = T.BIGINT
                cols.append(Column(vals, c.valid, typ, None))
            elif d[0] == "cntb":
                cols.append(Column(looked[:, d[1]].astype(jnp.int64),
                                   None, T.BIGINT, None))
            else:   # 'sumb'
                vals = looked[:, d[1]]
                if d[2] == "f":
                    cols.append(Column(vals.astype(jnp.float64), None,
                                       T.DOUBLE, None))
                else:
                    cols.append(Column(vals.astype(jnp.int64), None,
                                       T.BIGINT, None))
        for h in helpers:
            cols.append(Column(looked[:, h].astype(jnp.int64), None,
                               T.BIGINT, None))
        return Page(tuple(cols), page.num_rows).filter(live)

    return op


def agg_join_post(nk: int, derive, nhelpers: int, out_types):
    """Final shaping after the SINGLE aggregation over derived rows:
    re-type sums/counts to the plan's declared output types, restore SQL
    null semantics for build-side SUMs (NULL when the group saw no
    non-null build value — the summed #valid-w helper is the mask), and
    drop the helper columns. Returns op(agg_page) -> Page."""
    from trino_tpu.page import Column, Page
    derive = tuple(derive)
    out_types = tuple(out_types)

    def op(page):
        cols = list(page.columns[:nk])
        base = nk
        for i, (d, typ) in enumerate(zip(derive, out_types)):
            c = page.columns[base + i]
            if d[0] in ("pairs", "cntp", "cntb"):
                cols.append(Column(c.values, None, typ, None))
            elif d[0] == "sump":
                cols.append(Column(c.values, c.valid, typ, None))
            else:   # 'sumb'
                helper = page.columns[base + len(derive) + d[3]]
                cols.append(Column(c.values, helper.values > 0, typ,
                                   None))
        return Page(tuple(cols), page.num_rows)

    return op


def lookup_flops(rows: int, slots: int, ncols: int) -> int:
    """Cost-model MAC count of one blocked lookup dispatch (2 flops per
    multiply-accumulate — matches XLA's dot cost model), recorded on the
    query's mxu_flops counter per dispatch."""
    return 2 * int(rows) * int(slots) * int(ncols)
