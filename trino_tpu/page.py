"""Columnar Page/Column data model as JAX pytrees.

Reference parity: core/trino-spi/src/main/java/io/trino/spi/Page.java:33 and
spi/block/ (68 files). Design decisions (SURVEY.md §7.1):

- A Column = device value array + optional validity mask (replaces the Block
  hierarchy: nulls-as-bitmask instead of null flags per block kind).
- Strings are dictionary-encoded (spi/block/DictionaryBlock analog): device
  holds int32 codes; the host-side Dictionary holds the sorted string pool, so
  comparisons and ORDER BY on codes match string collation.
- A Page = tuple of equal-capacity Columns + a traced `num_rows` scalar. XLA
  needs static shapes, so pages have a static *capacity* (array length) and a
  dynamic row count; rows in [num_rows, capacity) are padding. Filters compact
  via a stable flag-sort (Page.filter), the device analog of
  Page.getPositions (spi/Page.java:332) / Block.copyPositions.
- Columns/Pages are registered pytrees so whole operator pipelines jit/shard
  cleanly; Type and Dictionary ride as static aux data (hash/eq by content
  fingerprint for dictionaries, so repeated pages of one table — and any
  OTHER table with a byte-identical pool — never retrace).
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T

_dict_ids = itertools.count()


# ---------------------------------------------------------------------------
# trace scopes: the operator names a device trace reads back (HLO metadata
# only — a scope changes no executable). Grammar `<family>__<tag>`, as
# exec/jit_cache.program_name. A kernel shared by several operators
# (Page.filter's compaction, ops/radix.py's passes) takes the family of
# the operator being traced, so a join's sorts count as join time.

# per thread: .family, .defer and .trace_notes while a program is being
# traced; .host_staged, below
_THREAD = threading.local()


@contextlib.contextmanager
def family_context(family: str):
    """Trace-time only: the operator family shared kernels belong to."""
    prev = getattr(_THREAD, "family", None)
    _THREAD.family = family
    try:
        yield
    finally:
        _THREAD.family = prev


@contextlib.contextmanager
def op_scope(name: str):
    """`jax.named_scope(name)` for one phase of an operator; `name` is
    `<family>__<tag>` and sets the family for the kernels it calls."""
    with family_context(name.partition("__")[0]), jax.named_scope(name):
        yield


def note_trace(fact: str) -> None:
    """Trace-time only: a static fact about the program being traced that
    its dispatcher wants to count (which form its direct aggregate took).
    Heard by `trace_notes`; said to nobody outside one."""
    notes = getattr(_THREAD, "trace_notes", None)
    if notes is not None:
        notes.add(fact)


def note_device(name: str, value) -> None:
    """Say a number the program computes — a scalar of its trace — to
    whoever runs it: heard by `device_notes`, said to nobody outside one.
    It leaves the program beside its outputs and is counted under `name`
    where a page's row count is (jit_cache.named)."""
    heard = getattr(_THREAD, "device_notes", None)
    if heard is not None:
        heard.append((name, value))


@contextlib.contextmanager
def device_notes():
    """Collect what the code traced inside says through `note_device`:
    [(name, scalar of that trace)]. A nested trace (a `shard_map`'s or a
    `lax.map`'s body) opens one of its own and drops it: its scalars may
    not leave it this way."""
    prev = getattr(_THREAD, "device_notes", None)
    heard = _THREAD.device_notes = []
    try:
        yield heard
    finally:
        _THREAD.device_notes = prev


@contextlib.contextmanager
def trace_notes():
    """Collect what the code traced inside says through `note_trace`
    (exec/jit_cache.named enters it around every program's trace). A
    program traced inside another tells the outer one too."""
    prev = getattr(_THREAD, "trace_notes", None)
    notes = _THREAD.trace_notes = set()
    try:
        yield notes
    finally:
        _THREAD.trace_notes = prev
        if prev is not None:
            prev |= notes


def host_staged_bytes() -> int:
    """Bytes this thread has moved host -> device through
    Column.from_numpy so far: the one door host data takes to the device."""
    return getattr(_THREAD, "host_staged", 0)


def count_host_staging(pages):
    """A page source's pages as (page, bytes the source moved host ->
    device to make it). Read around each pull: what the consumers stage
    between pulls is not the scan's."""
    it = iter(pages)
    while True:
        mark = host_staged_bytes()
        try:
            page = next(it)
        except StopIteration:
            return
        yield page, host_staged_bytes() - mark


def shared_scope(tag: str, default: str = "scan_filter"):
    """Scope of a kernel several operators share: `<caller's family>__tag`."""
    family = getattr(_THREAD, "family", None) or default
    return jax.named_scope(f"{family}__{tag}")


@contextlib.contextmanager
def defer_compaction(on: bool = True):
    """Trace-time only: while on, `Page.filter` keeps every lane where it
    is and hands its mask on as the page's selection. Entered by the chain
    composer (exec/local_planner.compose_chain) for a chain whose tail
    reads liveness from `row_mask()` alone; nothing else may enter it."""
    prev = getattr(_THREAD, "defer", False)
    _THREAD.defer = bool(on)
    try:
        yield
    finally:
        _THREAD.defer = prev


class Dictionary:
    """Host-side sorted string pool backing a dictionary-encoded column.

    Codes are indices into `values` (np.ndarray of str, ascending order), so
    integer comparison of codes == string comparison of values. Code -1 is
    reserved for padding. Hash/eq key on a CONTENT fingerprint so the pool
    can ride as jit-static aux data without object identity fragmenting
    the trace cache: two tables whose string pools are byte-identical
    (same data loaded twice, a re-created memory table, a re-generated
    connector pool) hit ONE trace for a warm canonical kernel instead of
    retracing per Dictionary object. Correctness: every host-side fold a
    trace bakes in (code_of, bounds, like/transform tables) is a pure
    function of the pool CONTENT, so content-equal pools are
    interchangeable within a trace. Eq compares fingerprints only — a
    16-byte blake2b over the pool — so trace-cache lookups stay O(1)
    instead of O(pool).
    """

    __slots__ = ("values", "id", "_table_cache", "_fp")

    def __init__(self, values: np.ndarray):
        import hashlib
        values = np.asarray(values, dtype=object)
        # ONE pass fuses the sortedness check (what makes device-side
        # <,>,min,max on codes correct) with the content fingerprint:
        # hashing at construction time means the pool bytes are walked
        # exactly once, while they are cache-hot from being built — a
        # lazily-hashed multi-GB pool used to stall the FIRST prepared
        # EXECUTE over a large string table by multiple milliseconds at
        # its first trace-cache lookup.
        h = hashlib.blake2b(digest_size=16)
        prev = None
        for s in values:
            if prev is not None and not (prev <= s):
                raise ValueError("dictionary must be sorted")
            prev = s
            b = s.encode("utf-8", "surrogatepass") \
                if isinstance(s, str) else repr(s).encode()
            h.update(len(b).to_bytes(4, "little"))
            h.update(b)
        self.values = values
        self.id = next(_dict_ids)
        self._fp = h.digest()   # content fingerprint, fixed at build

    @property
    def fingerprint(self) -> bytes:
        """Content digest of the pool (computed incrementally at
        construction): the jit-static identity of this dictionary."""
        return self._fp

    @classmethod
    def build(cls, strings: Sequence[str]) -> Tuple["Dictionary", np.ndarray]:
        """Encode `strings` -> (dictionary, int32 codes)."""
        uniq, codes = np.unique(np.asarray(strings, dtype=object),
                                return_inverse=True)
        return cls(uniq), codes.astype(np.int32)

    def code_of(self, s: str) -> int:
        """Exact-match lookup; -1 if absent (used to fold literals)."""
        i = int(np.searchsorted(self.values, s))
        if i < len(self.values) and self.values[i] == s:
            return i
        return -1

    def lower_bound(self, s: str) -> int:
        return int(np.searchsorted(self.values, s, side="left"))

    def upper_bound(self, s: str) -> int:
        return int(np.searchsorted(self.values, s, side="right"))

    def encode(self, strings: np.ndarray) -> np.ndarray:
        """Map strings -> int32 codes; raises KeyError if any value is absent."""
        arr = np.asarray(strings, dtype=object)
        if len(self.values) == 0:
            if len(arr) == 0:
                return np.empty(0, dtype=np.int32)
            raise KeyError("value(s) not present in dictionary")
        codes = np.searchsorted(self.values, arr).astype(np.int32)
        codes = np.minimum(codes, len(self.values) - 1)
        if not np.array_equal(self.values[codes], arr):
            raise KeyError("value(s) not present in dictionary")
        return codes

    def decode(self, codes: np.ndarray) -> np.ndarray:
        out = np.empty(len(codes), dtype=object)
        valid = codes >= 0
        out[valid] = self.values[codes[valid]]
        out[~valid] = None
        return out

    def __len__(self):
        return len(self.values)

    def __hash__(self):
        return hash(self.fingerprint)

    def __eq__(self, other):
        if self is other:
            return True
        if not isinstance(other, Dictionary):
            return NotImplemented
        return self.fingerprint == other.fingerprint

    def __repr__(self):  # pragma: no cover
        return f"Dictionary(id={self.id}, n={len(self.values)})"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Column:
    """One columnar vector. Reference: spi/block/Block.java:25.

    values : device array [capacity] of type.dtype — or, for ARRAY/MAP
             list layouts, [capacity, max_len] element planes
    valid  : optional bool device array [capacity]; None = no nulls
    type   : SQL Type (static)
    dictionary : for string types, the host string pool (static)
    lengths: for list layouts, int32 [capacity] live element counts
    aux    : for MAP, the per-element value plane [capacity, max_len]
             (keys live in `values` so map lookups search sorted keys)
    """

    values: jnp.ndarray
    valid: Optional[jnp.ndarray]
    type: T.Type
    dictionary: Optional[Dictionary] = None
    lengths: Optional[jnp.ndarray] = None
    aux: Optional[jnp.ndarray] = None
    aux_dictionary: Optional[Dictionary] = None

    def tree_flatten(self):
        children = [self.values]
        flags = [False, False]
        if self.valid is not None:
            children.append(self.valid)
            flags[0] = True
        extra = 0
        if self.lengths is not None:
            children.append(self.lengths)
            extra = 1
            if self.aux is not None:
                children.append(self.aux)
                extra = 2
        flags[1] = extra
        return tuple(children), (flags[0], flags[1], self.type,
                                 self.dictionary, self.aux_dictionary)

    @classmethod
    def tree_unflatten(cls, aux, children):
        has_valid, extra, typ, dictionary, aux_dict = aux
        it = iter(children)
        values = next(it)
        valid = next(it) if has_valid else None
        lengths = next(it) if extra >= 1 else None
        aux_arr = next(it) if extra >= 2 else None
        return cls(values, valid, typ, dictionary, lengths, aux_arr,
                   aux_dict)

    @property
    def capacity(self) -> int:
        return self.values.shape[0]

    def valid_mask(self) -> jnp.ndarray:
        """Always-materialized validity mask."""
        if self.valid is None:
            return jnp.ones(self.capacity, dtype=jnp.bool_)
        return self.valid

    def gather(self, indices: jnp.ndarray) -> "Column":
        """copyPositions analog (Block.java:250).

        Out-of-range indices clip to the last row: padding rows of a filtered
        page are garbage copies of a live row. INVARIANT: consumers must mask
        with Page.row_mask() — num_rows, not validity, delimits live rows.
        """
        values = jnp.take(self.values, indices, axis=0, mode="clip")
        valid = None
        if self.valid is not None:
            valid = jnp.take(self.valid, indices, mode="clip")
        lengths = None if self.lengths is None else \
            jnp.take(self.lengths, indices, mode="clip")
        aux = None if self.aux is None else \
            jnp.take(self.aux, indices, axis=0, mode="clip")
        return Column(values, valid, self.type, self.dictionary, lengths,
                      aux, self.aux_dictionary)

    def with_valid(self, valid: Optional[jnp.ndarray]) -> "Column":
        return Column(self.values, valid, self.type, self.dictionary,
                      self.lengths, self.aux, self.aux_dictionary)

    @property
    def nbytes(self) -> int:
        """Device bytes (values + validity) — the unit of memory accounting
        shared by the HBM pool (exec/memory.py) and scan caches."""
        n = int(getattr(self.values, "nbytes", 0) or 0)
        for a in (self.valid, self.lengths, self.aux):
            if a is not None:
                n += int(getattr(a, "nbytes", 0) or 0)
        return n

    @classmethod
    def from_numpy(cls, data: np.ndarray, typ: T.Type,
                   valid: Optional[np.ndarray] = None,
                   dictionary: Optional[Dictionary] = None) -> "Column":
        if T.is_string(typ) and dictionary is None:
            dictionary, codes = Dictionary.build(data)
            data = codes
        arr = jnp.asarray(np.asarray(data, dtype=T.to_numpy_dtype(typ)))
        v = None if valid is None else jnp.asarray(valid, dtype=jnp.bool_)
        _THREAD.host_staged = host_staged_bytes() + arr.nbytes \
            + (0 if v is None else v.nbytes)
        return cls(arr, v, typ, dictionary)

    def to_numpy(self, num_rows: Optional[int] = None) -> np.ndarray:
        """Decode back to host values (python objects for strings/nulls).

        Slices on DEVICE before transfer: pages have large static capacities
        (scan pages are table-sized), and fetching the full padded array over
        a remote-TPU link costs capacity/num_rows times the useful bytes."""
        n = self.capacity if num_rows is None else int(num_rows)
        vals = np.asarray(self.values[:n])
        if self.dictionary is not None:
            out = self.dictionary.decode(vals)
        else:
            out = vals.astype(object)
        if self.valid is not None:
            mask = ~np.asarray(self.valid[:n])
            out = out.copy()
            out[mask] = None
        return out


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SplitColumn:
    """A 64-bit integer column as its low and high 32-bit words, two
    buffers: the form in which a store keeps a column whose pages a
    program walks (`in_chunks`). The TPU has no 64-bit lanes; a program
    given an int64 operand first splits the WHOLE of it into these two
    arrays (`X64SplitLow` / `X64SplitHigh`), which fuses into the reader
    where the reader takes the operand whole and is a copy as long as the
    column where a loop cuts pages from it: 1.95 GB of temporaries for
    q1's four decimals at SF10 (PR 43, compiled for the described chip).
    Split once, when stored, a page's words are cut by the loop and
    joined in registers."""

    low: jnp.ndarray            # uint32 [capacity]
    high: jnp.ndarray           # uint32 [capacity]
    valid: Optional[jnp.ndarray]
    type: T.Type
    dtype: Any                  # the column's: int64 or uint64 (static)
    dictionary: Optional[Dictionary] = None

    def tree_flatten(self):
        children = (self.low, self.high) + (
            () if self.valid is None else (self.valid,))
        return children, (self.type, self.dtype, self.dictionary)

    @classmethod
    def tree_unflatten(cls, aux, children):
        typ, dtype, dictionary = aux
        low, high, *valid = children
        return cls(low, high, valid[0] if valid else None, typ, dtype,
                   dictionary)

    @staticmethod
    def splits(column: Column) -> bool:
        """Whether `column` is one this form is for."""
        v = column.values
        return v.ndim == 1 and v.dtype.itemsize == 8 \
            and v.dtype.kind in "iu" and column.lengths is None

    @classmethod
    def of(cls, column: Column) -> "SplitColumn":
        low, high = _split_words(column.values)
        return cls(low, high, column.valid, column.type,
                   column.values.dtype, column.dictionary)

    @property
    def capacity(self) -> int:
        return self.low.shape[0]

    @property
    def nbytes(self) -> int:
        return sum(int(getattr(a, "nbytes", 0) or 0)
                   for a in (self.low, self.high, self.valid)
                   if a is not None)

    def like(self, values) -> Column:
        """The Column of these words' type over `values`."""
        return Column(values, self.valid, self.type, self.dictionary)

    def column(self) -> Column:
        """The words joined: inside a program, over a page of them, two
        converts that fuse into whatever reads the column."""
        wide = self.low.astype(jnp.uint64) \
            | (self.high.astype(jnp.uint64) << 32)
        return self.like(wide.astype(self.dtype))


@jax.jit
def _split_words(values):
    return (values.astype(jnp.uint32),
            (values >> 32).astype(jnp.uint32))


def _running_count(mask: jnp.ndarray) -> jnp.ndarray:
    """Inclusive int32 running count of a 1-D mask, as a two-level scan
    (rows of up to 1024, then the row totals) wherever the length splits
    evenly. Same values as `jnp.cumsum`; the flat form's compile time on
    the TPU swings between 1 s and 20 s with the length (PR 23: 19.8 s at
    1 048 576, 4.6 s at 4 194 304), the blocked form stays near 1 s."""
    n = mask.shape[0]
    x = mask.astype(jnp.int32)
    block = 1
    while block < 1024 and n % (2 * block) == 0:
        block *= 2
    if block < 8 or n // block < 2:
        return jnp.cumsum(x)
    inner = jnp.cumsum(x.reshape(n // block, block), axis=1)
    totals = inner[:, -1]
    return (inner + (jnp.cumsum(totals) - totals)[:, None]).reshape(n)


def shift_left(x: jnp.ndarray, s: int) -> jnp.ndarray:
    """Lane p takes lane p + s along axis 0 (s static); the tail takes
    zeros."""
    tail = jnp.zeros((s,) + x.shape[1:], x.dtype)
    return jnp.concatenate([x[s:], tail], axis=0)


def shift_takes(mask: jnp.ndarray):
    """Who moves when, for `Page.filter`'s compaction under `mask` (its
    docstring has the argument): -> (takes, kept count), bit k of
    `takes[p]` set where lane p takes lane p + 2^k in round k. A kept row
    moves left by d = the dropped rows before it; only d has to run the
    rounds to say so."""
    capacity = mask.shape[0]
    with shared_scope("compact_slots"):
        kept = _running_count(mask)
        count = kept[-1]
        lane = jnp.arange(1, capacity + 1, dtype=jnp.int32)
        d = jnp.where(mask, lane - kept, 0)
    with shared_scope("compact_shift"):
        takes = jnp.zeros(capacity, dtype=jnp.int32)
        s = 1
        while s < capacity:
            d_left = shift_left(d, s)
            take = (d_left & s) != 0
            takes = takes | jnp.where(take, s, 0)
            d = jnp.where(take, d_left, jnp.where((d & s) != 0, 0, d))
            d, takes = jax.lax.optimization_barrier((d, takes))
            s *= 2
    return takes, count


def shift_move(a: jnp.ndarray, takes: jnp.ndarray, capacity: int,
               tag: str = "compact_shift"):
    """One array (1-D, or planes along axis 0) through the rounds that
    `takes` spells, a round at a time (the barriers): the scheduler holds
    two copies of one array and not of the page
    (tests/test_tpu_compile.py). -> (moved array, takes): the next array
    takes `takes` from here, so it starts when this one is done.
    `capacity` is the mask's; `a` and `takes` may hold several arrays of
    that many lanes end to end, each with the mask's `takes`: no lane
    takes from beyond its own array's end. `tag` names the rounds' scope."""
    with shared_scope(tag):
        lanes = (a.shape[0],) + (1,) * (a.ndim - 1)
        s = 1
        while s < capacity:
            take = ((takes & s) != 0).reshape(lanes)
            a = jnp.where(take, shift_left(a, s), a)
            a, takes = jax.lax.optimization_barrier((a, takes))
            s *= 2
    return a, takes


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Page:
    """Horizontal batch of Columns + dynamic row count.

    Reference: spi/Page.java:33. `num_rows` may be a traced scalar under jit;
    `capacity` (static) is the shared array length of all columns.

    `selection`, when present, is a boolean lane mask: the live rows are
    those of the prefix [0, num_rows) that it marks. Only a deferred
    `filter` makes one, and only inside a fused chain (see `filter`): a
    page that leaves its program has none, and everything that reads
    rows by position (`to_host`, `shrink_to`, `pad_to`, `gather`, the
    concat helpers) refuses one.
    """

    columns: Tuple[Column, ...]
    num_rows: jnp.ndarray  # int32 scalar (python int ok outside jit)
    selection: Optional[jnp.ndarray] = None

    def tree_flatten(self):
        return (tuple(self.columns), self.num_rows, self.selection), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        columns, num_rows, selection = children
        return cls(tuple(columns), num_rows, selection)

    @property
    def capacity(self) -> int:
        return self.columns[0].capacity if self.columns else 0

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def column(self, i: int) -> Column:
        return self.columns[i]

    def row_mask(self) -> jnp.ndarray:
        """Mask of live rows: the prefix [0, num_rows), less what a
        deferred filter dropped (`selection`). The one definition of
        liveness."""
        live = jnp.arange(self.capacity, dtype=jnp.int32) < self.num_rows
        return live if self.selection is None else live & self.selection

    def with_selection(self, selection: Optional[jnp.ndarray]) -> "Page":
        return Page(self.columns, self.num_rows, selection)

    def _require_compact(self, what: str) -> None:
        if self.selection is not None:
            raise ValueError(
                f"{what}: the page carries a selection mask (a deferred "
                "filter); only a fused chain's mask-consuming tail may "
                "read it")

    def append_column(self, col: Column) -> "Page":
        return Page(self.columns + (col,), self.num_rows, self.selection)

    def select_columns(self, indices: Sequence[int]) -> "Page":
        return Page(tuple(self.columns[i] for i in indices), self.num_rows,
                    self.selection)

    def filter(self, mask: jnp.ndarray) -> "Page":
        """Keep the rows where mask is true (Page.getPositions analog).

        jit-safe: the output keeps this page's capacity either way. This
        is the form for a caller INSIDE a program, which cannot know the
        kept count; a host driver that has fetched it compacts with
        `compact_to` at the count's own capacity instead.

        Compacting (the default): selected rows move to the front and
        num_rows becomes the selected count. Every consumer that reads
        rows by position needs this: joins, sorts, TopN, pass-through
        partial states, pages that leave their program.

        Deferred (while the chain composer holds `defer_compaction`, i.e.
        in a fused chain of lane-wise steps that ends in the partial hash
        aggregate): no row moves. The columns and num_rows stay, and the
        mask ANDed with the rows live so far becomes the page's
        `selection`, which `row_mask()` folds in and every aggregation
        path reads. The permutation and one gather per column, 63 % of
        the SF10 scan cell's device time (PERF.md, PR 25), are not
        emitted at all.

        Compaction: shift-and-select, with no index. A kept row moves
        left by d = the number of dropped rows before it, and d never
        falls from one kept row to the next. In round k (the low bit
        first) every kept row whose d has bit k set moves left by
        s = 2^k: each array of each column becomes
        `where(take, x shifted by s, x)`, a lane-wise select between an
        array and itself shifted by a static power of two. log2(capacity)
        rounds; no permutation, no scatter, no sort, no gather.
        Kept rows never collide: for kept i < j the lanes between them
        hold j - i - 1 >= d_j - d_i - 1 other rows, so
        j - i >= 1 + d_j - d_i; after the rounds below k row i sits at
        i - (d_i mod 2^k), and (d_j mod 2^k) - (d_i mod 2^k) is
        d_j - d_i or less, so j still sits to the right of i, and a row
        that moves in round k lands on a lane whose own row moves too or
        that no row holds. `d` rides along and reads 0 on a lane that
        holds no kept row, so such a lane is never taken from. Only d
        has to run the rounds to say who takes when; its answers are
        kept as the bits of one int32 a lane, and every array of every
        column (`values`, `valid`, `lengths`, `aux`; 2-D planes along
        axis 0) then moves alone: a round reads the bits and reads and
        writes the array and its shifted copy, 36 B a lane of an int64
        column, 20 rounds at 1 048 576 lanes. One array at a time keeps
        the temporaries at two copies of a column, not of the page
        (0.54 GB for 33 554 432 lanes x 5 int64, where the permutation
        and its gathers took 0.40). What lies behind the kept prefix is
        whatever the rounds left there.
        On a v5e, fenced (PERF.md, PR 35, step 0): one page of 1 048 576
        lanes x 4 int64 at keep share 0.54 takes 2.0 ms (2.7 s to
        compile) against 72.7 ms (1.8 s) for the permutation and its
        gathers, 2.3 ms (5.7 s) against 83.0 ms with one validity mask,
        whatever the keep share (no round reads the data it moves);
        33 554 432 lanes x 5 int64 take 312 ms against 8 617 ms (all
        arrays a round: 289 ms, at 2.96 GB of temporaries). Inside q3's
        chains a page's rounds are 0.5 ms of device time.
        Roads not taken: a permutation (a running count and an int32
        scatter) with one gather per column costs by the index, 18-25 M
        elements/s on a v5e whatever the operand (PERF.md, PR 31) — it
        is `compact_to`'s form, where the host's count cuts the indices
        to the kept rung. Carrying the columns as payload of one
        `lax.sort` ran at 1.4 ms against the gather's 8 ms for eight
        columns of 65 536 rows, but the TPU compiler builds a sort
        network per operand: 290 s of compile time for five int64
        operands, paid in EVERY fused chain (PR 23).
        """
        mask = mask & self.row_mask()
        if getattr(_THREAD, "defer", False):
            return self.with_selection(mask)
        if not self.columns:
            return Page((), jnp.sum(mask).astype(jnp.int32))
        takes, count = shift_takes(mask)
        arrays, tree = jax.tree_util.tree_flatten(self.columns)
        moved = []
        for a in arrays:
            a, takes = shift_move(a, takes, self.capacity)
            moved.append(a)
        columns = jax.tree_util.tree_unflatten(tree, moved)
        return Page(columns, count)

    def _partition_perm(self, mask: jnp.ndarray):
        """(permutation, kept count) of a stable partition by `mask`: a
        running count gives every row its target slot (kept rows to the
        front, dropped rows behind them, both in input order — the
        permutation a stable sort on the drop-flag produces) and one
        int32 scatter inverts it. (The TPU compiler makes of that scatter
        a two-operand 32-bit sort of the lanes and a sorted scatter:
        0.24 s for 33 554 432 lanes on a v5e, whatever the mask; PERF.md,
        PR 31.)"""
        with shared_scope("compact_slots"):
            kept = _running_count(mask)
            count = kept[-1]
            idx = jnp.arange(self.capacity, dtype=jnp.int32)
            target = jnp.where(mask, kept - 1, count + idx - kept)
            perm = jnp.zeros(self.capacity, dtype=jnp.int32).at[target].set(
                idx, unique_indices=True, mode="promise_in_bounds")
        return perm, count

    def compact_to(self, mask: jnp.ndarray, capacity: int) -> "Page":
        """`filter(mask).shrink_to(capacity)`, lane for lane, without the
        lanes that `shrink_to` would slice off ever being gathered: the
        same permutation, then every column gathered through its first
        `capacity` entries only. Output capacity `capacity` (static),
        num_rows the kept count, kept rows in input order.

        For a host driver that holds the kept count before it compacts
        (the join's probe path, exec/local_planner._compact_counted): the
        caller promises count <= capacity. Never deferred — the page it
        returns leaves its program — and, reading rows by position, it
        refuses a page that carries a selection."""
        self._require_compact("compact_to")
        if capacity > self.capacity:
            raise ValueError("compact_to cannot grow a page")
        perm, count = self._partition_perm(mask & self.row_mask())
        with shared_scope("compact_gather"):
            head = perm[:capacity]
            return Page(tuple(c.gather(head) for c in self.columns), count)

    def gather(self, indices: jnp.ndarray, count) -> "Page":
        self._require_compact("gather")
        cols = tuple(c.gather(indices) for c in self.columns)
        return Page(cols, jnp.asarray(count, dtype=jnp.int32))

    def shrink_to(self, capacity: int) -> "Page":
        """Drop padding: slice every column to a smaller static capacity.

        Live rows are always a prefix (row_mask is `arange < num_rows`), so
        this is a pure O(capacity) device slice. Host-side only: the caller
        must know num_rows <= capacity (e.g. after a batched count fetch).
        Blocking operators shrink oversized intermediates so sorts/builds
        run at live size instead of scan-page capacity."""
        self._require_compact("shrink_to")
        if capacity >= self.capacity:
            return self
        cols = tuple(
            Column(c.values[:capacity],
                   None if c.valid is None else c.valid[:capacity],
                   c.type, c.dictionary,
                   None if c.lengths is None else c.lengths[:capacity],
                   None if c.aux is None else c.aux[:capacity],
                   c.aux_dictionary)
            for c in self.columns)
        return Page(cols, self.num_rows)

    def pad_to(self, capacity: int) -> "Page":
        """Grow capacity (static) without changing live rows."""
        self._require_compact("pad_to")
        if capacity < self.capacity:
            raise ValueError("pad_to cannot shrink")
        if capacity == self.capacity:
            return self
        extra = capacity - self.capacity
        cols = []
        for c in self.columns:
            values = jnp.concatenate(
                [c.values, jnp.zeros((extra,), dtype=c.values.dtype)])
            valid = None
            if c.valid is not None:
                valid = jnp.concatenate(
                    [c.valid, jnp.zeros((extra,), dtype=jnp.bool_)])
            cols.append(Column(values, valid, c.type, c.dictionary))
        return Page(tuple(cols), self.num_rows)

    @classmethod
    def from_numpy(cls, arrays: Sequence[np.ndarray], typs: Sequence[T.Type],
                   valids: Optional[Sequence[Optional[np.ndarray]]] = None,
                   dictionaries: Optional[Sequence[Optional[Dictionary]]] = None,
                   ) -> "Page":
        n = len(arrays[0]) if arrays else 0
        valids = valids or [None] * len(arrays)
        dictionaries = dictionaries or [None] * len(arrays)
        cols = tuple(
            Column.from_numpy(a, t, v, d)
            for a, t, v, d in zip(arrays, typs, valids, dictionaries))
        return cls(cols, jnp.asarray(n, dtype=jnp.int32))

    def to_host(self, num_rows: Optional[int] = None) -> list:
        """All columns as decoded host arrays in ONE batched transfer.
        List (ARRAY/MAP) columns decode to python lists / dicts per row."""
        self._require_compact("to_host")
        n = int(self.num_rows) if num_rows is None else num_rows
        # the eager `x[:k]` is an executable a length k, and an answer's
        # length moves with its parameters (Q13: 45 or 46 rows): cut on
        # the device at the power of two above n — one executable a rung —
        # and to n on the host
        k = min(self.capacity, 1 << max(n - 1, 0).bit_length())

        def head(x):
            return None if x is None else x[:k]
        fetch = [(head(c.values), head(c.valid), head(c.lengths),
                  head(c.aux)) for c in self.columns]
        host = jax.device_get(fetch)
        out = []
        for c, cut in zip(self.columns, host):
            vals, valid, lengths, aux = (
                None if x is None else x[:n] for x in cut)
            if lengths is not None:
                rows = np.empty(n, dtype=object)
                for i in range(n):
                    ln = int(lengths[i])
                    elems = vals[i, :ln]
                    if c.dictionary is not None:
                        elems = c.dictionary.decode(elems)
                    if aux is not None:
                        avals = aux[i, :ln]
                        if c.aux_dictionary is not None:
                            avals = c.aux_dictionary.decode(avals)
                            avals = avals.tolist()
                        else:
                            avals = avals.tolist()
                        rows[i] = dict(zip(elems.tolist(), avals))
                    else:
                        rows[i] = list(elems.tolist())
                decoded = rows
            elif c.dictionary is not None:
                decoded = c.dictionary.decode(vals)
            else:
                decoded = vals.astype(object)
            if valid is not None:
                decoded = decoded.copy()
                decoded[~valid] = None
            out.append(decoded)
        return out

    def to_pylist(self) -> list:
        """Rows as python tuples (client-result materialization)."""
        n = int(self.num_rows)
        cols = self.to_host(n)
        return [tuple(col[i] for col in cols) for i in range(n)]


def union_dictionaries(dicts: Sequence[Dictionary]
                       ) -> Tuple[Dictionary, list]:
    """Rebase N dictionaries onto one union pool.

    Returns (union_dictionary, [int32 device remap array per input dict]):
    new_code = remap[i][old_code]. Host-side, static — callers cache per
    dictionary identity (DictionaryBlock 'compact to shared pool' analog)."""
    union = Dictionary(np.unique(np.concatenate([d.values for d in dicts])))
    remaps = [jnp.asarray(np.searchsorted(union.values, d.values)
                          .astype(np.int32)) for d in dicts]
    return union, remaps


def concat_pages(pages: Sequence[Page]) -> Page:
    """Host-side page concatenation (not jit-safe; used at stage boundaries).

    Transfer discipline (every device->host fetch is a sync that drains
    the dispatch queue): ONE batched device_get for all row counts, then
    ONE for every column slice of every page — never a fetch per column.
    Slices are taken on device so only live rows cross PCIe, not padded
    capacity.
    """
    if not pages:
        raise ValueError("no pages")
    for p in pages:
        p._require_compact("concat_pages")
    if len(pages) == 1:
        return pages[0]
    ncols = pages[0].num_columns
    counts = [int(c) for c in jax.device_get([p.num_rows for p in pages])]
    total = sum(counts)
    for ci in range(ncols):
        ref = pages[0].column(ci)
        if any(p.column(ci).dictionary != ref.dictionary for p in pages):
            raise ValueError(
                f"column {ci}: pages use different dictionaries; re-encode "
                "to a shared dictionary before concatenating")
    needs_valid = [any(p.column(ci).valid is not None for p in pages)
                   for ci in range(ncols)]
    fetch = []
    for p, c in zip(pages, counts):
        for ci in range(ncols):
            col = p.column(ci)
            fetch.append(col.values[:c])
            if needs_valid[ci]:
                fetch.append(col.valid_mask()[:c])
    host = jax.device_get(fetch)
    it = iter(host)
    vparts: list = [[] for _ in range(ncols)]
    nparts: list = [[] for _ in range(ncols)]
    for p, c in zip(pages, counts):
        for ci in range(ncols):
            vparts[ci].append(next(it))
            if needs_valid[ci]:
                nparts[ci].append(next(it))
    cols = []
    for ci in range(ncols):
        ref = pages[0].column(ci)
        values = jnp.asarray(np.concatenate(vparts[ci])) if total \
            else ref.values[:0]
        valid = None
        if needs_valid[ci]:
            valid = jnp.asarray(np.concatenate(nparts[ci])) if total \
                else ref.valid_mask()[:0]
        cols.append(Column(values, valid, ref.type, ref.dictionary))
    return Page(tuple(cols), jnp.asarray(total, dtype=jnp.int32))


def device_concat(pages: Sequence[Page]) -> Page:
    """Concatenate pages ON DEVICE into one page of capacity sum(capacities).

    jit-safe (traced num_rows; static capacities): each page's FULL-capacity
    column is written with lax.dynamic_update_slice at the running live
    offset, in page order — page i+1's write starts where page i's live rows
    end, so it overwrites page i's padding tail; whatever garbage the last
    page leaves beyond the total live count is ordinary output padding
    (row_mask never reads it). Pure HBM-bandwidth copies — no host round
    trip (concat_pages bounces every live row through the host and syncs
    the device to do it) and no sort pass.

    All pages must share column types/dictionaries (caller contract, same
    as concat_pages)."""
    if not pages:
        raise ValueError("no pages")
    for p in pages:
        p._require_compact("device_concat")
    if len(pages) == 1:
        return pages[0]
    ncols = pages[0].num_columns
    for ci in range(ncols):
        ref = pages[0].column(ci)
        if any(p.column(ci).dictionary != ref.dictionary for p in pages):
            raise ValueError(
                f"column {ci}: pages use different dictionaries; re-encode "
                "to a shared dictionary before concatenating")
    out_cap = sum(p.capacity for p in pages)
    counts = [p.num_rows.astype(jnp.int64) for p in pages]
    offs = []
    off = jnp.int64(0)
    for c in counts:
        offs.append(off)
        off = off + c
    total = off
    needs_valid = [any(p.column(ci).valid is not None for p in pages)
                   for ci in range(ncols)]
    cols = []
    for ci in range(ncols):
        ref = pages[0].column(ci)
        if ref.lengths is not None:
            # list columns: pad element planes to the widest page's L
            lmax = max(p.column(ci).values.shape[1] for p in pages)

            def plane(get):
                out2 = jnp.zeros((out_cap, lmax), dtype=get(ref).dtype)
                for p, o in zip(pages, offs):
                    a = get(p.column(ci))
                    if a.shape[1] < lmax:
                        a = jnp.pad(a, ((0, 0), (0, lmax - a.shape[1])))
                    out2 = jax.lax.dynamic_update_slice(out2, a, (o, 0))
                return out2
            values2 = plane(lambda c: c.values)
            aux2 = plane(lambda c: c.aux) if ref.aux is not None else None
            lens = jnp.zeros(out_cap, dtype=jnp.int32)
            for p, o in zip(pages, offs):
                lens = jax.lax.dynamic_update_slice(
                    lens, p.column(ci).lengths, (o,))
            valid = None
            if needs_valid[ci]:
                valid = jnp.zeros(out_cap, dtype=jnp.bool_)
                for p, o in zip(pages, offs):
                    valid = jax.lax.dynamic_update_slice(
                        valid, p.column(ci).valid_mask(), (o,))
            cols.append(Column(values2, valid, ref.type, ref.dictionary,
                               lens, aux2, ref.aux_dictionary))
            continue
        out = jnp.zeros(out_cap, dtype=ref.values.dtype)
        for p, o in zip(pages, offs):
            out = jax.lax.dynamic_update_slice(out, p.column(ci).values,
                                               (o,))
        valid = None
        if needs_valid[ci]:
            valid = jnp.zeros(out_cap, dtype=jnp.bool_)
            for p, o in zip(pages, offs):
                valid = jax.lax.dynamic_update_slice(
                    valid, p.column(ci).valid_mask(), (o,))
        cols.append(Column(out, valid, ref.type, ref.dictionary))
    return Page(tuple(cols), total.astype(jnp.int32))


def in_chunks(page: Page, body: Callable[[Page], Page], lanes: int,
              span=None) -> Page:
    """`body` over `page` a chunk of `lanes` lanes at a time (`lax.map`:
    one chunk's temporaries live at once), the chunks' output rows
    compacted into one page. For bodies whose outputs may be merged by
    concatenation: partial aggregation states. Live rows of each chunk
    come from the page's row count, clipped to the chunk.

    Without `span` every chunk of the page is walked, read from the
    columns reshaped to [chunks, lanes] (the capacity is a multiple of
    `lanes`): the mesh lowering's form, kept as it was — on one chip,
    over whole int64 columns, it read 6-15 x the time of the other and
    gigabytes of temporaries (PERF.md section 6, PR 43). With `span` =
    (first chunk, chunks) — the first may be traced, the number is
    static — that many chunks from there, each cut
    from the flat columns by a `dynamic_slice` (no copy of the span; the
    capacity need not be a multiple): one executable walks any span of any
    page of these shapes, and a chunk past the live rows is read clamped
    and counts none of its lanes."""
    if span is None:
        k = page.capacity // lanes
        starts = jnp.arange(k, dtype=jnp.int32) * lanes
        cols = jax.tree_util.tree_map(
            lambda x: x.reshape((k, lanes) + x.shape[1:]), page.columns)

        def chunk(xs):
            return xs
    else:
        first, k = span
        starts = (first + jnp.arange(k, dtype=jnp.int32)) * lanes
        cols = starts

        def chunk(at):
            return jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_slice_in_dim(x, at, lanes),
                page.columns)
    rows = jnp.clip(page.num_rows - starts, 0, lanes).astype(jnp.int32)

    def a_chunk(xs):
        with device_notes():        # a scalar of the map's body stays there
            return body(Page(chunk(xs[0]), xs[1]))
    outs = jax.lax.map(a_chunk, (cols, rows))
    m = outs.columns[0].values.shape[1]
    live = (jnp.arange(m, dtype=jnp.int32)[None, :]
            < outs.num_rows[:, None]).reshape(k * m)
    flat = jax.tree_util.tree_map(
        lambda x: x.reshape((k * m,) + x.shape[2:]), outs.columns)
    return Page(flat, jnp.int32(k * m)).filter(live)
