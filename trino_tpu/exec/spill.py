"""Spill-to-host partition store + device hash partitioner.

Reference parity: spiller/ (FileSingleStreamSpiller.java,
GenericPartitioningSpiller.java) + operator/aggregation/builder/
SpillableHashAggregationBuilder.java:47, re-thought for this topology:
the scarce resource is HBM and single-op scratch, while the HOST has
tens of GB of RAM behind the chip's PCIe link — so "disk" is host memory and
the spill unit is a hash PARTITION (Grace aggregation), not a sorted
run. Each over-budget batch is group-compacted (Step.INTERMEDIATE),
partition-sorted ON DEVICE by a mix64 of its group keys, fetched in one
transfer, and split host-side at partition boundaries; finalization
re-stages one bounded partition at a time. The same store backs sort
spill (range partitions instead of hash).
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.errors import EXCEEDED_SPILL_LIMIT, TrinoError
from trino_tpu.page import Column, Page

_SM1 = np.uint64(0xBF58476D1CE4E5B9)
_SM2 = np.uint64(0x94D049BB133111EB)
_NULL_TAG = np.uint64(0x9E3779B97F4A7C15)
_GOLDEN = 0x9E3779B97F4A7C15
_U64 = (1 << 64) - 1


def _mix64(x: jnp.ndarray) -> jnp.ndarray:
    x = (x ^ (x >> 30)) * _SM1
    x = (x ^ (x >> 27)) * _SM2
    return x ^ (x >> 31)


def _canonical_key_hash(page: Page, key_channels: Sequence[int]
                        ) -> jnp.ndarray:
    """Per-row u64 hash of the group key tuple with NULLs canonicalized
    (every NULL in a column hashes identically — a group's rows MUST land
    in one partition; join's _key_u64 treats null keys as dead instead)."""
    acc = jnp.zeros(page.capacity, dtype=jnp.uint64)
    for ch in key_channels:
        c = page.column(ch)
        v = c.values
        if v.dtype == jnp.bool_:
            u = v.astype(jnp.uint64)
        elif jnp.issubdtype(v.dtype, jnp.floating):
            u = jax.lax.bitcast_convert_type(
                v.astype(jnp.float64) + 0.0, jnp.uint64)
        else:
            u = v.astype(jnp.uint64)
        if c.valid is not None:
            u = jnp.where(c.valid, u, _NULL_TAG)
        acc = _mix64(acc ^ _mix64(u))
    return acc


def _partition_sort(page: Page, pid: jnp.ndarray, npart: int):
    """ONE stable sort moves each partition's rows together (dead rows
    route past the last partition); the caller fetches the live prefix in
    one transfer and slices at the counts' offsets."""
    live = page.row_mask()
    pid = jnp.where(live, pid, npart)
    payload = []
    for c in page.columns:
        payload.append(c.values)
        if c.valid is not None:
            payload.append(c.valid)
    out = jax.lax.sort([pid] + payload, num_keys=1, is_stable=True)
    it = iter(out[1:])
    cols = []
    for c in page.columns:
        values = next(it)
        valid = next(it) if c.valid is not None else None
        cols.append(Column(values, valid, c.type, c.dictionary))
    counts = jax.ops.segment_sum(
        live.astype(jnp.int64), pid, num_segments=npart + 1)[:npart]
    return Page(tuple(cols), page.num_rows), counts


def partition_by_hash(key_channels: Sequence[int], npart: int,
                      salt: int = 0):
    """op(page) -> (page sorted by partition id, int64 counts[npart]).

    `salt` derives an independent hash family per recursion depth: a
    partition that misses its budget repartitions with salt = depth so
    its keys REDISTRIBUTE instead of all landing in one child again
    (rows of any single key still colocate — required for
    correctness — at every salt). salt=0 is byte-identical to the
    historical hash, so warm kernel-cache keys stay valid."""
    key_channels = tuple(key_channels)
    salt_mix = jnp.uint64((_GOLDEN * (int(salt) + 1)) & _U64) \
        if salt else None

    def op(page: Page):
        h = _canonical_key_hash(page, key_channels)
        if salt_mix is not None:
            h = _mix64(h ^ salt_mix)
        pid = (h % jnp.uint64(npart)).astype(jnp.int32)
        return _partition_sort(page, pid, npart)

    return op


def leading_rank(channel: int, ascending: bool, nulls_first: bool):
    """Monotonic u64 rank of ONE sort key: ascending rank order == the
    key's OUTPUT order, with direction, NULL placement and NaN-largest
    folded in. Range-partitioning on this rank keeps ties (equal leading
    keys) inside one partition, so per-partition full sorts compose into
    a correct global order (the sort-spill invariant)."""

    def op(page: Page) -> jnp.ndarray:
        c = page.column(channel)
        v = c.values
        if v.dtype == jnp.bool_:
            u = v.astype(jnp.uint64)
        elif jnp.issubdtype(v.dtype, jnp.floating):
            # NaN canonicalizes to +inf: it RANKS with +inf (same
            # partition), and the per-partition full sort orders NaN
            # after +inf via its own nan-flag sub-key
            f = v.astype(jnp.float64)
            f = jnp.where(jnp.isnan(f), jnp.inf, f) + 0.0
            bits = jax.lax.bitcast_convert_type(f, jnp.uint64)
            neg = bits >> 63 == 1
            u = jnp.where(neg, ~bits, bits | jnp.uint64(1) << 63)
        elif jnp.issubdtype(v.dtype, jnp.unsignedinteger):
            u = v.astype(jnp.uint64)
        else:
            u = v.astype(jnp.uint64) ^ (jnp.uint64(1) << 63)
        if not ascending:
            u = ~u
        # reserve the extremes for NULLs
        u = (u >> 2) + jnp.uint64(1)
        if c.valid is not None:
            null_rank = jnp.uint64(0) if nulls_first \
                else jnp.uint64(0xFFFFFFFFFFFFFFFF)
            u = jnp.where(c.valid, u, null_rank)
        return u

    return op


def rank_bounds(npart: int):
    """op(ranks, num_rows) -> u64 bounds[npart-1]: quantile split points
    of the live ranks (dead rows sort to the top via u64 max)."""

    def op(ranks: jnp.ndarray, live: jnp.ndarray, num_rows) -> jnp.ndarray:
        masked = jnp.where(live, ranks, jnp.uint64(0xFFFFFFFFFFFFFFFF))
        s = jax.lax.sort([masked], num_keys=1)[0]
        q = (jnp.arange(1, npart, dtype=jnp.int64)
             * num_rows.astype(jnp.int64)) // npart
        return jnp.take(s, q, mode="clip")

    return op


def partition_by_range(channel: int, ascending: bool, nulls_first: bool,
                       npart: int):
    """op(page, bounds) -> (page sorted by range partition id, counts).
    side='right' keeps every row equal to a boundary value in one
    partition (multi-key ties must not straddle partitions)."""
    rank = leading_rank(channel, ascending, nulls_first)

    def op(page: Page, bounds: jnp.ndarray):
        r = rank(page)
        pid = jnp.searchsorted(bounds, r, side="right").astype(jnp.int32)
        return _partition_sort(page, pid, npart)

    return op


class ExceededSpillLimitError(TrinoError, RuntimeError):
    """A spill reservation would push the query past its host-RAM spill
    budget (`spill_max_bytes`): classified, non-retryable — re-running
    spills the same bytes again (ExceededSpillLimitException analog)."""

    CODE = EXCEEDED_SPILL_LIMIT


def default_spill_limit_bytes() -> int:
    """The session default for `spill_max_bytes` when unset (0): half of
    physical host RAM — the host side of the topology is the spill
    device, and leaving half for everything else keeps the OOM killer
    (the OS one) out of the picture."""
    try:
        total = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
        return max(int(total) // 2, 1 << 30)
    except (AttributeError, OSError, ValueError):
        return 64 << 30


def resolve_spill_limit(session) -> int:
    """Session `spill_max_bytes`; 0 = the host-RAM-derived default."""
    v = int(session.get("spill_max_bytes"))
    return v if v > 0 else default_spill_limit_bytes()


class SpillLedger:
    """Process-wide host-RAM accounting for spill partition stores (the
    NODE_POOL discipline applied to the HOST side): every store charges
    its pieces here per query and frees them on drop/close, so the
    `trino_tpu_spill_bytes` gauge reads what spill actually holds and an
    over-budget query fails with a CLASSIFIED error instead of silently
    exhausting host RAM."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reserved = 0
        self.peak = 0
        self.denials = 0
        self.by_query: Dict[str, int] = {}

    def reserve(self, nbytes: int, query_id: str,
                limit: Optional[int]) -> None:
        nbytes = int(nbytes)
        if nbytes <= 0:
            return
        with self._lock:
            held = self.by_query.get(query_id, 0)
            if limit is not None and held + nbytes > limit:
                self.denials += 1
                raise ExceededSpillLimitError(
                    f"Query exceeded spill limit of {_fmt_bytes(limit)} "
                    f"[spill store requested {_fmt_bytes(nbytes)} with "
                    f"{_fmt_bytes(held)} spilled]")
            self.by_query[query_id] = held + nbytes
            self.reserved += nbytes
            self.peak = max(self.peak, self.reserved)

    def release(self, nbytes: int, query_id: str) -> None:
        nbytes = int(nbytes)
        if nbytes <= 0:
            return
        with self._lock:
            held = self.by_query.get(query_id, 0)
            freed = min(nbytes, held)
            if held - freed <= 0:
                self.by_query.pop(query_id, None)
            else:
                self.by_query[query_id] = held - freed
            self.reserved = max(0, self.reserved - freed)


# the process singleton every store charges (host RAM is shared)
SPILL_LEDGER = SpillLedger()


def _fmt_bytes(n: int) -> str:
    from trino_tpu.exec.memory import _fmt_bytes as fmt
    return fmt(int(n))


def _pow2(n: int) -> int:
    return max(1 << max(int(n) - 1, 0).bit_length(), 8)


class HostPartitionStore:
    """Per-partition host-RAM pieces of spilled pages.

    A piece is [(values_np, valid_np|None)] per column; `meta` captures
    (type, dictionary) per column from the first spill (all spilled pages
    share one layout — same plan node). Byte-accounted per partition and
    — when a ledger is attached — against the process SpillLedger under
    the owning query's `spill_max_bytes` budget."""

    def __init__(self, npart: int, ledger: Optional[SpillLedger] = None,
                 query_id: str = "", limit: Optional[int] = None):
        self.npart = npart
        self.pieces: List[List[list]] = [[] for _ in range(npart)]
        self.meta: Optional[List[Tuple[T.Type, object]]] = None
        self.bytes = 0
        self.part_bytes = [0] * npart
        self.ledger = ledger
        self.query_id = query_id
        self.limit = limit

    # --------------------------------------------------- byte accounting

    def _settle(self, p: int, delta: int) -> None:
        """Charge (positive) or release (negative) partition p's bytes,
        mirrored into the ledger. Charges can raise
        ExceededSpillLimitError — callers charge BEFORE appending."""
        if delta > 0:
            if self.ledger is not None:
                self.ledger.reserve(delta, self.query_id, self.limit)
            self.bytes += delta
            self.part_bytes[p] += delta
        elif delta < 0:
            if self.ledger is not None:
                self.ledger.release(-delta, self.query_id)
            self.bytes = max(0, self.bytes + delta)
            self.part_bytes[p] = max(0, self.part_bytes[p] + delta)

    @staticmethod
    def _piece_bytes(piece) -> int:
        return sum(v.nbytes + (m.nbytes if m is not None else 0)
                   for v, m in piece)

    def spill_partitioned(self, page: Page, counts: np.ndarray) -> None:
        """Fetch a partition-sorted page's live rows in ONE transfer and
        slice at partition offsets."""
        counts = np.asarray(counts, dtype=np.int64)
        total = int(counts.sum())
        if total == 0:
            return
        if self.meta is None:
            self.meta = [(c.type, c.dictionary) for c in page.columns]
        fetch = []
        for c in page.columns:
            fetch.append(c.values[:total])
            fetch.append(None if c.valid is None else c.valid[:total])
        got = jax.device_get([f for f in fetch if f is not None])
        it = iter(got)
        host_cols = []
        for c in page.columns:
            vals = np.asarray(next(it))
            valid = None if c.valid is None else np.asarray(next(it))
            host_cols.append((vals, valid))
        offs = np.concatenate([[0], np.cumsum(counts)])
        for p in range(self.npart):
            lo, hi = int(offs[p]), int(offs[p + 1])
            if hi <= lo:
                continue
            piece = [(vals[lo:hi],
                      None if valid is None else valid[lo:hi])
                     for vals, valid in host_cols]
            self._settle(p, self._piece_bytes(piece))
            self.pieces[p].append(piece)

    def add_piece(self, p: int, piece) -> None:
        """Append a host-built piece (heavy-key splitting) with the same
        accounting as a device spill."""
        self._settle(p, self._piece_bytes(piece))
        self.pieces[p].append(piece)

    def partition_rows(self, p: int) -> int:
        return sum(len(piece[0][0]) for piece in self.pieces[p])

    def partition_bytes(self, p: int) -> int:
        return self.part_bytes[p]

    def chunk_rows_for(self, p: int, budget_bytes: int) -> int:
        """Rows per bounded restage chunk so one staged chunk stays
        within `budget_bytes` (floor 4096 keeps degenerate budgets from
        devolving into row-at-a-time staging)."""
        rows = self.partition_rows(p)
        if rows <= 0:
            return 4096
        per_row = max(1, self.part_bytes[p] // rows)
        return max(4096, int(budget_bytes) // per_row)

    def _stage(self, spans, n: int,
               capacity: Optional[int] = None) -> Page:
        """Build ONE device page from host (piece, lo, hi) spans."""
        capacity = capacity if capacity is not None else _pow2(max(n, 1))
        cols = []
        for ci in range(len(self.meta)):
            vals = np.concatenate(
                [piece[ci][0][lo:hi] for piece, lo, hi in spans])
            has_valid = any(piece[ci][1] is not None
                            for piece, lo, hi in spans)
            valid = None
            if has_valid:
                valid = np.concatenate(
                    [piece[ci][1][lo:hi] if piece[ci][1] is not None
                     else np.ones(hi - lo, dtype=bool)
                     for piece, lo, hi in spans])
            typ, d = self.meta[ci]
            pv = np.zeros(capacity, dtype=vals.dtype)
            pv[:n] = vals
            pm = None
            if valid is not None:
                pm = np.zeros(capacity, dtype=bool)
                pm[:n] = valid
            cols.append(Column(jnp.asarray(pv),
                               None if pm is None else jnp.asarray(pm),
                               typ, d))
        return Page(tuple(cols), jnp.asarray(n, dtype=jnp.int32))

    def restage(self, p: int, capacity: int) -> Optional[Page]:
        """Concatenate partition p host-side and stage ONE device page."""
        if not self.pieces[p] or self.meta is None:
            return None
        n = self.partition_rows(p)
        spans = [(piece, 0, len(piece[0][0])) for piece in self.pieces[p]]
        return self._stage(spans, n, capacity)

    def iter_partition_chunks(self, p: int,
                              chunk_rows: int) -> Iterator[Page]:
        """Partition p as bounded device pages of <= chunk_rows live rows
        each — the restage transient of an over-budget partition never
        exceeds one chunk (recursion, chunked folds, chunked-build joins
        all pull through this). Does NOT drop the partition, so a caller
        can iterate it repeatedly (the chunked-build join re-streams the
        probe partition per build chunk)."""
        if not self.pieces[p] or self.meta is None:
            return
        chunk_rows = max(int(chunk_rows), 1)
        spans = []
        acc = 0
        for piece in self.pieces[p]:
            n = len(piece[0][0])
            lo = 0
            while lo < n:
                take = min(chunk_rows - acc, n - lo)
                spans.append((piece, lo, lo + take))
                acc += take
                lo += take
                if acc == chunk_rows:
                    yield self._stage(spans, acc)
                    spans, acc = [], 0
        if spans:
            yield self._stage(spans, acc)

    def drain_partition_chunks(self, p: int,
                               chunk_rows: int) -> Iterator[Page]:
        """iter_partition_chunks that RELEASES each piece (bytes back to
        the ledger, host array refs dropped) as soon as its last row has
        been staged — single-pass consumers (recursive repartition into
        a child store, chunked folds) never double-hold a partition's
        bytes against the spill budget while transferring it."""
        if not self.pieces[p] or self.meta is None:
            return
        chunk_rows = max(int(chunk_rows), 1)
        pieces = self.pieces[p]
        spans = []
        acc = 0
        done: List[list] = []
        while pieces:
            piece = pieces.pop(0)
            n = len(piece[0][0])
            lo = 0
            while lo < n:
                take = min(chunk_rows - acc, n - lo)
                spans.append((piece, lo, lo + take))
                acc += take
                lo += take
                if acc == chunk_rows:
                    yield self._stage(spans, acc)
                    spans, acc = [], 0
                    # pieces fully covered by now-staged spans release;
                    # the current piece may still have unstaged rows
                    for d in done:
                        self._settle(p, -self._piece_bytes(d))
                    done = []
            done.append(piece)
        if spans:
            yield self._stage(spans, acc)
        for d in done:
            self._settle(p, -self._piece_bytes(d))

    def drop(self, p: int) -> None:
        self._settle(p, -self.part_bytes[p])
        self.pieces[p] = []

    def close(self) -> None:
        """Release every partition (generator finally blocks call this so
        an abandoned or failed operator can never strand ledger bytes)."""
        for p in range(self.npart):
            self.drop(p)


# ---------------------------------------------------------------------------
# host-side heavy-hitter detection + splitting (the per-partition analog of
# parallel/exchange.detect_heavy_keys' top-k discipline, over spilled pieces)

_NP_SM1 = np.uint64(0xBF58476D1CE4E5B9)
_NP_SM2 = np.uint64(0x94D049BB133111EB)
_NP_NULL_TAG = np.uint64(_GOLDEN)


def _np_mix64(x: np.ndarray) -> np.ndarray:
    x = (x ^ (x >> np.uint64(30))) * _NP_SM1
    x = (x ^ (x >> np.uint64(27))) * _NP_SM2
    return x ^ (x >> np.uint64(31))


def _np_piece_key_hash(piece, key_idxs: Sequence[int]) -> np.ndarray:
    """Host mirror of `_canonical_key_hash` over one spilled piece: the
    composite-key identity heavy detection and splitting group rows by.
    (It need not match the DEVICE hash bit-for-bit — it only has to be
    consistent across pieces and across the two sides of a join.)"""
    n = len(piece[0][0])
    acc = np.zeros(n, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for ci in key_idxs:
            vals, valid = piece[ci]
            if vals.dtype == np.bool_:
                u = vals.astype(np.uint64)
            elif np.issubdtype(vals.dtype, np.floating):
                u = (vals.astype(np.float64) + 0.0).view(np.uint64)
            else:
                u = vals.astype(np.uint64)
            if valid is not None:
                u = np.where(valid, u, _NP_NULL_TAG)
            acc = _np_mix64(acc ^ _np_mix64(u))
    return acc


def partition_key_hashes(store: HostPartitionStore, p: int,
                         key_idxs: Sequence[int]) -> List[np.ndarray]:
    """Per-piece canonical key hashes of one partition — computed ONCE
    and shared by detection + splitting (the pieces are exactly the
    large spilled partitions these paths exist for)."""
    return [_np_piece_key_hash(piece, key_idxs)
            for piece in store.pieces[p]]


def detect_partition_heavy_keys(store: HostPartitionStore, p: int,
                                key_idxs: Sequence[int], limit: int,
                                min_count: int,
                                piece_hashes=None) -> np.ndarray:
    """Top-`limit` key identities of partition p whose row count reaches
    `min_count` (uint64 canonical hashes). A heavy key is exactly what
    recursive repartitioning can NEVER split — every row of one key
    re-hashes to one child at any salt — so these are split out into the
    dedicated bounded paths instead of recursing forever."""
    if not store.pieces[p]:
        return np.empty(0, dtype=np.uint64)
    if piece_hashes is None:
        piece_hashes = partition_key_hashes(store, p, key_idxs)
    hashes = np.concatenate(piece_hashes)
    keys, counts = np.unique(hashes, return_counts=True)
    mask = counts >= max(int(min_count), 1)
    keys, counts = keys[mask], counts[mask]
    if len(keys) > int(limit):
        top = np.argsort(counts)[::-1][:int(limit)]
        keys = keys[top]
    return keys


def split_partition(store: HostPartitionStore, p: int,
                    key_idxs: Sequence[int],
                    heavy: np.ndarray,
                    piece_hashes=None) -> HostPartitionStore:
    """Move partition p's rows whose key identity is in `heavy` into a
    NEW single-partition store (same ledger/budget); the source keeps the
    rest. Pure host work — no device round trip. `piece_hashes` reuses
    the detection pass's per-piece hashes (must align with the
    partition's piece list at call time)."""
    sub = HostPartitionStore(1, ledger=store.ledger,
                             query_id=store.query_id, limit=store.limit)
    sub.meta = None if store.meta is None else list(store.meta)
    old_bytes = store.part_bytes[p]
    rest_pieces: List[list] = []
    heavy_pieces: List[list] = []
    if piece_hashes is None:
        piece_hashes = partition_key_hashes(store, p, key_idxs)
    for piece, h in zip(store.pieces[p], piece_hashes):
        mask = np.isin(h, heavy)
        if not mask.any():
            # no heavy rows here: keep the piece BY REFERENCE — a
            # fancy-indexed all-True copy would double host RAM traffic
            # on exactly the memory-pressure path this split relieves
            rest_pieces.append(piece)
            continue
        heavy_pieces.append(
            [(v[mask], None if m is None else m[mask])
             for v, m in piece])
        if not mask.all():
            keep = ~mask
            rest_pieces.append(
                [(v[keep], None if m is None else m[keep])
                 for v, m in piece])
    # settle: release the whole old partition first, then re-charge the
    # two halves — a transient double-charge could trip the budget for
    # bytes that already live in host RAM
    store.pieces[p] = []
    store._settle(p, -old_bytes)
    for piece in rest_pieces:
        store.add_piece(p, piece)
    for piece in heavy_pieces:
        sub.add_piece(0, piece)
    return sub
