"""Collective exchanges: the shuffle data plane as ICI collectives.

Reference parity (SURVEY §2.8): PartitionedOutputOperator + OutputBuffer +
HttpPageBufferClient + ExchangeClient — all replaced by in-program
collectives. These functions run INSIDE a shard_map over QueryMesh.AXIS:

  all_to_all_by_key : FIXED_HASH_DISTRIBUTION repartition. Rows are radix-
                      bucketed by key hash, compacted per destination, and
                      exchanged with lax.all_to_all. Fixed per-peer bucket
                      capacity keeps shapes static; the returned overflow
                      count is psum'd so the host can re-run with a larger
                      bucket (same contract as the join/page capacity ladder).
  broadcast_page    : FIXED_BROADCAST — all_gather the build side.
  gather_page       : SINGLE distribution — all_gather + shard-0 consumption
                      (coordinator-only stages read one replica).

Hash function matches ops/join._mix64 (splitmix64) so co-partitioned joins
land build/probe rows of one key on one shard.

Skew (JSPIM heavy-hitter-aware partitioning): plain hash routing sends
EVERY row of one hot key to one shard — a single skewed key overloads a
chip while the rest idle (TPC-DS catalog/web fact joins). detect_heavy_keys
finds globally-frequent keys in-program (local run lengths -> top-k
candidates -> all_gather -> global counts); the join exchange then SPREADS
heavy probe rows round-robin across the mesh and REPLICATES the matching
build rows to every shard, so correctness is preserved (each probe row
still sees all of its key's build rows exactly once) while no shard
receives more than ~1/n of a hot key's probe rows.

Names (`page.op_scope`, the grammar `benchmark/trace_programs.py` reads):
the work that prepares rows for the wire runs under `exchange__partition`
(destination hash, bucket sort, scatter into per-peer buckets) and
`exchange__heavy_keys`, the collectives themselves under
`exchange__all_to_all`, `exchange__broadcast` (all_gather),
`exchange__all_gather` (the heavy-key candidates) and `exchange__psum`,
and the receiver's compaction under `exchange__compact` — so the time in
the wire can be told from the time around it.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu.ops.join import _key_u64, _mix64
from trino_tpu.ops.radix import stable_argsort
from trino_tpu.page import Column, Page, op_scope

AXIS = "workers"

_U64MAX = np.uint64(0xFFFFFFFFFFFFFFFF)


def _is_heavy(key: jnp.ndarray, heavy: jnp.ndarray) -> jnp.ndarray:
    """Row mask: key value appears in the (sentinel-padded) heavy set."""
    hk = heavy[None, :]
    return ((key[:, None] == hk) & (hk != _U64MAX)).any(axis=1)


def _partition_of(page: Page, key_channels: Sequence[int],
                  n_parts: int,
                  heavy: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    key, is_null = _key_u64(page, key_channels)
    part = (_mix64(key) % jnp.uint64(n_parts)).astype(jnp.int32)
    # null keys route to shard 0 (they never match joins/group as equals is
    # handled downstream; they just need a deterministic home)
    part = jnp.where(is_null, 0, part)
    if heavy is not None:
        # spread mode: rows of a heavy key round-robin over the mesh by
        # row position instead of hammering the key's hash shard
        idx = jnp.arange(page.capacity, dtype=jnp.uint64)
        spread = ((_mix64(key) + idx) % jnp.uint64(n_parts)) \
            .astype(jnp.int32)
        part = jnp.where(_is_heavy(key, heavy) & ~is_null, spread, part)
    return jnp.where(page.row_mask(), part, n_parts)  # dead rows -> dropped


def detect_heavy_keys(page: Page, key_channels: Sequence[int], k: int,
                      min_global_count: int, axis: str = AXIS
                      ) -> jnp.ndarray:
    """Globally-frequent key detection, entirely in-program (JSPIM's
    heavy-hitter pre-pass as a collective): each shard sorts its keys,
    takes its k most frequent as candidates, all_gathers the (n*k)
    candidate (key, count) pairs, and sums counts across shards per
    candidate. Returns a [k] uint64 vector of raw key values whose global
    count reaches min_global_count, padded with the u64 sentinel.

    A truly heavy key is in the local top-k of every shard where it is
    frequent, so the global sum is exact for the keys that matter;
    borderline keys may be undercounted and simply stay un-spread."""
    n = jax.lax.psum(1, axis)
    with op_scope("exchange__heavy_keys"):
        key, is_null = _key_u64(page, key_channels)
        live = page.row_mask() & ~is_null
        masked = jnp.where(live, key, _U64MAX)
        s = jnp.sort(masked)
        cap = page.capacity
        idx = jnp.arange(cap, dtype=jnp.int32)
        boundary = (s != jnp.roll(s, 1)).at[0].set(True)
        run_start = jax.lax.cummax(jnp.where(boundary, idx, 0))
        nxt = jnp.where(boundary, idx, cap)
        suffix_min = jnp.flip(jax.lax.cummin(jnp.flip(nxt)))
        next_start = jnp.concatenate(
            [suffix_min[1:], jnp.full((1,), cap, dtype=suffix_min.dtype)])
        run_len = (next_start - run_start).astype(jnp.int32)
        cand_count = jnp.where(boundary & (s != _U64MAX), run_len, 0)
        top_counts, top_idx = jax.lax.top_k(cand_count, k)
        cand_keys = jnp.take(s, top_idx)
    with op_scope("exchange__all_gather"):
        all_keys = jax.lax.all_gather(cand_keys, axis).reshape(n * k)
        all_counts = jax.lax.all_gather(top_counts, axis).reshape(n * k)
    with op_scope("exchange__heavy_keys"):
        eq = all_keys[:, None] == all_keys[None, :]
        glob = jnp.sum(eq * all_counts[None, :].astype(jnp.int64), axis=1)
        nk = n * k
        first = ~jnp.any(
            eq & (jnp.arange(nk)[None, :] < jnp.arange(nk)[:, None]), axis=1)
        score = jnp.where((all_keys != _U64MAX) & first
                          & (glob >= min_global_count), glob, -1)
        sel_score, sel = jax.lax.top_k(score, k)
        return jnp.where(sel_score > 0, jnp.take(all_keys, sel), _U64MAX)


def _send_columns(page: Page, src: jnp.ndarray):
    """The send buffer's columns: slot j carries row `src[j]`. One gather
    per column (a scatter of 46 M lanes takes the v5e 1.0-1.4 s, a gather
    a fraction of that), and a validity mask only for a column that has
    nulls: which slots are occupied travels once, beside the columns."""
    return [Column(jnp.take(c.values, src, mode="clip"),
                   None if c.valid is None
                   else jnp.take(c.valid, src, mode="clip"),
                   c.type, c.dictionary) for c in page.columns]


def _exchange_compact(cols, occ, n: int, bucket_capacity: int,
                      axis: str) -> Page:
    """The receive half of an all_to_all exchange: swap the per-destination
    buckets over the mesh and move the live rows to a dense prefix so
    downstream operators see a normal page. Every sender fills a bucket
    from its front, so the live rows of the receive buffer are n dense
    runs: row j of the output is found from the runs' lengths alone, and
    each column is one gather (no sort, no scatter)."""
    def a2a(x):
        return jax.lax.all_to_all(
            x.reshape(n, bucket_capacity, *x.shape[1:]), axis,
            split_axis=0, concat_axis=0).reshape(n * bucket_capacity,
                                                 *x.shape[1:])

    with op_scope("exchange__all_to_all"):
        occ_recv = a2a(occ)
        received = [(a2a(c.values),
                     None if c.valid is None else a2a(c.valid))
                    for c in cols]
    with op_scope("exchange__compact"):
        counts = jnp.sum(occ_recv.reshape(n, bucket_capacity), axis=1,
                         dtype=jnp.int32)
        ends = jnp.cumsum(counts)
        j = jnp.arange(n * bucket_capacity, dtype=jnp.int32)
        # the run output row j lies in: how many runs end at or before it
        peer = jnp.zeros_like(j)
        for p in range(n - 1):
            peer = peer + (j >= ends[p]).astype(jnp.int32)
        src = peer * bucket_capacity + j - jnp.take(ends - counts, peer)
        out_cols = [Column(jnp.take(vals, src, mode="clip"),
                           None if valid is None
                           else jnp.take(valid, src, mode="clip"),
                           c.type, c.dictionary)
                    for c, (vals, valid) in zip(cols, received)]
        return Page(tuple(out_cols), ends[-1])


def all_to_all_by_key(page: Page, key_channels: Sequence[int],
                      bucket_capacity: int, axis: str = AXIS,
                      heavy: Optional[jnp.ndarray] = None
                      ) -> Tuple[Page, jnp.ndarray]:
    """Hash-repartition rows across the mesh axis.

    Returns (page_of_rows_now_owned_by_this_shard, global_overflow_count).
    Overflow > 0 means some source shard had more than bucket_capacity rows
    for one destination; the host re-runs the stage with a bigger bucket.

    `heavy` (optional [k] uint64 from detect_heavy_keys) engages SPREAD
    mode: rows of heavy keys round-robin across all shards instead of hash
    routing — the probe half of the skew-aware join exchange (the build
    half replicates via all_to_all_replicate with the SAME heavy set).
    """
    n = jax.lax.psum(1, axis)
    with op_scope("exchange__partition"):
        part = _partition_of(page, key_channels, n, heavy=heavy)
        # rows in destination order (dead rows last), each destination's
        # rows a run: slot (d, r) of the send buffer takes the r-th row
        # of run d, by one gather through the sort's permutation
        order = stable_argsort([part])
        counts = jnp.stack([jnp.sum(part == d, dtype=jnp.int32)
                            for d in range(n)])
        starts = jnp.cumsum(counts) - counts
        overflow_local = jnp.sum(jnp.maximum(counts - bucket_capacity, 0))
        slot = jnp.arange(n * bucket_capacity, dtype=jnp.int32)
        dest, rank = slot // bucket_capacity, slot % bucket_capacity
        occ = rank < jnp.take(counts, dest)
        src = jnp.take(order, jnp.take(starts, dest) + rank, mode="clip")
        cols = _send_columns(page, src)
    out = _exchange_compact(cols, occ, n, bucket_capacity, axis)
    with op_scope("exchange__psum"):
        total_overflow = jax.lax.psum(overflow_local, axis)
    return out, total_overflow


def all_to_all_replicate(page: Page, key_channels: Sequence[int],
                         bucket_capacity: int, heavy: jnp.ndarray,
                         axis: str = AXIS) -> Tuple[Page, jnp.ndarray]:
    """Skew-aware build-side repartition: rows of non-heavy keys hash-route
    as usual; rows of heavy keys are REPLICATED into every destination's
    bucket, so each shard holds the full build set for the keys whose probe
    rows were spread across the mesh (JSPIM heavy-hitter replication).

    Returns (page, global_overflow_count) with the same overflow-ladder
    contract as all_to_all_by_key."""
    n = jax.lax.psum(1, axis)
    with op_scope("exchange__partition"):
        key, is_null = _key_u64(page, key_channels)
        live = page.row_mask()
        hpart = (_mix64(key) % jnp.uint64(n)).astype(jnp.int32)
        hpart = jnp.where(is_null, 0, hpart)
        hvy = _is_heavy(key, heavy) & ~is_null
        total_slots = n * bucket_capacity
        overflow_local = jnp.int32(0)
        # the row each slot carries: one scatter of row numbers per
        # destination, then every column is a gather through them
        rows = jnp.arange(page.capacity, dtype=jnp.int32)
        src = jnp.full((total_slots,), page.capacity, dtype=jnp.int32)
        for d in range(n):
            m = live & ((hpart == d) | hvy)
            rank = jnp.cumsum(m, dtype=jnp.int32) - 1
            overflow_local = overflow_local + jnp.maximum(
                jnp.sum(m, dtype=jnp.int32) - bucket_capacity, 0)
            ok = m & (rank < bucket_capacity)
            src = src.at[jnp.where(ok, d * bucket_capacity + rank,
                                   total_slots)].set(rows, mode="drop")
        occ = src < page.capacity
        cols = _send_columns(page, src)
    out = _exchange_compact(cols, occ, n, bucket_capacity, axis)
    with op_scope("exchange__psum"):
        return out, jax.lax.psum(overflow_local, axis)


def broadcast_page(page: Page, axis: str = AXIS) -> Page:
    """Replicate every shard's rows to all shards (build-side broadcast).

    Output capacity = n * input capacity; rows keep their liveness via the
    row-count scalar recomputed from per-shard counts.
    """
    n = jax.lax.psum(1, axis)
    my_rows = page.num_rows

    def gather(x):
        g = jax.lax.all_gather(x, axis)  # (n, cap, ...)
        return g.reshape(n * x.shape[0], *x.shape[1:])

    with op_scope("exchange__broadcast"):
        rows_per_shard = jax.lax.all_gather(my_rows, axis)  # (n,)
        gathered = [(gather(c.values),
                     None if c.valid is None else gather(c.valid))
                    for c in page.columns]
    with op_scope("exchange__compact"):
        cap = page.capacity
        idx = jnp.arange(n * cap, dtype=jnp.int32)
        shard_of = idx // cap
        within = idx % cap
        live = within < jnp.take(rows_per_shard, shard_of)
        # compact live rows to the front
        perm = stable_argsort([~live])
        cols = [Column(jnp.take(vals, perm),
                       None if valid is None
                       else jnp.take(valid & live, perm),
                       c.type, c.dictionary)
                for c, (vals, valid) in zip(page.columns, gathered)]
        return Page(tuple(cols),
                    jnp.sum(rows_per_shard).astype(jnp.int32))


def gather_page(page: Page, axis: str = AXIS) -> Page:
    """SINGLE distribution: every shard receives all rows; the host reads
    shard 0's replica (coordinator-only consumption)."""
    return broadcast_page(page, axis)
