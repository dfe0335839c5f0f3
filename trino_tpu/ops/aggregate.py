"""Hash aggregation as sort-based segment reduction.

Reference parity: operator/HashAggregationOperator.java + the group-by hashes
(MultiChannelGroupByHash.java:853, BigintGroupByHash.java:425) and codegen'd
accumulators (operator/aggregation/AccumulatorCompiler.java:80).

TPU design: instead of an open-addressing hash table (pointer-chasing, bad fit
for the VPU), group-by = lexicographic `lax.sort` on the key columns, segment
boundary detection, then `jax.ops.segment_*` reductions — O(n log n) but
entirely vectorized, fusible, and deterministic. Distributed plans split the
work into PARTIAL (pre-exchange, per shard) and FINAL (post-exchange) steps
exactly like PushPartialAggregationThroughExchange.java; aggregate *state* is
a tuple of columns (e.g. avg = (sum, count)), mirroring the reference's
serialized accumulator states.

Null semantics: GROUP BY treats NULL as a regular group (null-first in the
sort key); aggregates skip NULL inputs; SUM/AVG/MIN/MAX of zero non-null rows
is NULL, COUNT is 0.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from trino_tpu import types as T
from trino_tpu.ops.radix import in_order, sorted_by
from trino_tpu.page import (Column, Page, note_device, note_trace, op_scope,
                            shift_left, shift_move, shift_takes)


class Step:
    """Aggregation step (reference: operator/aggregation/AggregationNode.Step).

    INTERMEDIATE merges partial states and re-emits the PARTIAL layout —
    the spillable-aggregation compaction step
    (MergingHashAggregationBuilder.java analog): the executor folds an
    over-budget buffer of partial pages into one group-compacted partial
    page before deciding whether to spill it."""

    SINGLE = "single"
    PARTIAL = "partial"
    INTERMEDIATE = "intermediate"
    FINAL = "final"


@dataclasses.dataclass(frozen=True)
class StateColumn:
    """One column of aggregate state.

    contrib: (values, valid_mask) -> per-row contribution array
    reducer: 'sum' | 'min' | 'max' — also how partial states merge
    """

    type: T.Type
    contrib: Callable
    reducer: str


@dataclasses.dataclass(frozen=True)
class AggregateFunction:
    """Declarative aggregate: state columns + final projection.

    final: (state_value_arrays, nonnull_counts_or_None) -> (values, valid|None)
    """

    name: str
    state: Callable[[T.Type], Tuple[StateColumn, ...]]
    final: Callable
    output_type: Callable[[Optional[T.Type]], T.Type]


def _sum_state(in_type):
    acc_t = T.DOUBLE if isinstance(in_type, (T.DoubleType, T.RealType)) else T.BIGINT
    if isinstance(in_type, T.DecimalType):
        acc_t = in_type
    return (
        StateColumn(acc_t, lambda v, m: jnp.where(m, v, 0).astype(acc_t.dtype), "sum"),
        StateColumn(T.BIGINT, lambda v, m: m.astype(jnp.int64), "sum"),  # nnz
    )


def _sum_final(state, _):
    total, nnz = state
    return total, nnz > 0


def _count_state(in_type):
    return (StateColumn(T.BIGINT, lambda v, m: m.astype(jnp.int64), "sum"),)


def _count_final(state, _):
    return state[0], None


def _minmax_state(in_type, is_min):
    dt = in_type.dtype
    ident = _ident_for(jnp.dtype(dt), is_min)
    red = "min" if is_min else "max"
    return (
        StateColumn(in_type, lambda v, m: jnp.where(m, v, ident).astype(dt), red),
        StateColumn(T.BIGINT, lambda v, m: m.astype(jnp.int64), "sum"),
    )


def _minmax_final(state, _):
    value, nnz = state
    return value, nnz > 0


def _avg_state(in_type):
    if isinstance(in_type, T.DecimalType):
        sum_t = in_type
    else:
        sum_t = T.DOUBLE
    return (
        StateColumn(sum_t, lambda v, m: jnp.where(m, v, 0).astype(sum_t.dtype), "sum"),
        StateColumn(T.BIGINT, lambda v, m: m.astype(jnp.int64), "sum"),
    )


def _avg_final_factory(in_type):
    def final(state, _):
        total, nnz = state
        denom = jnp.maximum(nnz, 1)
        if isinstance(in_type, T.DecimalType):
            # decimal avg keeps scale, HALF_UP
            half = jax.lax.div(denom, jnp.int64(2))
            adj = jnp.where(total >= 0, total + half, total - half)
            value = jax.lax.div(adj, denom)
        else:
            value = total.astype(jnp.float64) / denom
        return value, nnz > 0
    return final


def _to_double(v, t: Optional[T.Type]):
    """Numeric column -> float64 true value (descale decimals)."""
    out = v.astype(jnp.float64)
    if isinstance(t, T.DecimalType) and t.scale:
        out = out / (10.0 ** t.scale)
    return out


def _count_if_state(in_type):
    return (StateColumn(T.BIGINT,
                        lambda v, m: (v & m).astype(jnp.int64), "sum"),)


def _bool_state(is_and):
    # AND folds with min over {0,1} (identity 1), OR with max (identity 0)
    ident = 1 if is_and else 0
    red = "min" if is_and else "max"
    return (
        StateColumn(T.BIGINT,
                    lambda v, m: jnp.where(m, v.astype(jnp.int64), ident),
                    red),
        StateColumn(T.BIGINT, lambda v, m: m.astype(jnp.int64), "sum"),
    )


def _bool_final(state, _):
    value, nnz = state
    return value > 0, nnz > 0


def _hash64(v: jnp.ndarray) -> jnp.ndarray:
    """splitmix64 over value bits (floats canonicalized so SQL-equal values
    hash equal) — the XxHash64 role in HLL/checksum states."""
    if jnp.issubdtype(v.dtype, jnp.floating):
        v = jax.lax.bitcast_convert_type(v.astype(jnp.float64) + 0.0,
                                         jnp.uint64)
    x = v.astype(jnp.uint64)
    x = (x + jnp.uint64(0x9E3779B97F4A7C15))
    x = (x ^ (x >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> 27)) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> 31)


def _checksum_state(in_type):
    """Order-independent checksum: wrapping int64 sum of per-value hashes.
    Reference: operator/aggregation/ChecksumAggregationFunction — which
    emits varbinary(8); here the same 64 bits surface as BIGINT. The mask
    folds NULL rows to a zero contribution (the reference hashes SQL NULL
    to a constant — observable only when comparing checksums across
    engines, out of scope for the BIGINT surface)."""
    def contrib(v, m):
        return jnp.where(m, _hash64(v).astype(jnp.int64), 0)
    return (StateColumn(T.BIGINT, contrib, "sum"),
            StateColumn(T.BIGINT, lambda v, m: m.astype(jnp.int64), "sum"))


def _checksum_final(state, _):
    total, nnz = state
    # NULL over zero non-null rows (ChecksumAggregationFunction)
    return total.astype(jnp.int64), nnz > 0


_HLL_P = 11            # 2^11 = 2048 registers -> standard error 2.30%
_HLL_M = 1 << _HLL_P


def _hll_register_inputs(vals, elig):
    h = _hash64(vals)
    bucket = (h >> jnp.uint64(64 - _HLL_P)).astype(jnp.int32)
    w = (h & jnp.uint64((1 << 53) - 1)).astype(jnp.float64)
    # rho = 53 - floor(log2(w)) for w>0 (P(rho=r) = 2^-r), 54 when w == 0;
    # ints < 2^53 are exact in float64, so floor(log2) is exact
    rho = jnp.where(w > 0,
                    53 - jnp.floor(jnp.log2(jnp.maximum(w, 1.0))),
                    54.0).astype(jnp.int32)
    return jnp.where(elig, bucket, 0), jnp.where(elig, rho, 0)


def _hll_estimate(sum_present, cnt_present):
    """Raw HLL estimator + small-range linear counting (absent buckets
    contribute 2^0 = 1). Reference:
    operator/aggregation/ApproximateCountDistinctAggregation + airlift
    HyperLogLog."""
    m = float(_HLL_M)
    alpha = 0.7213 / (1.0 + 1.079 / m)
    sum_full = sum_present + (m - cnt_present)
    est = alpha * m * m / jnp.maximum(sum_full, 1e-12)
    zeros = m - cnt_present
    lc = m * jnp.log(m / jnp.maximum(zeros, 1.0))
    use_lc = (est <= 2.5 * m) & (zeros > 0)
    return jnp.round(jnp.where(use_lc, lc, est)).astype(jnp.int64)


def _hll_grouped(page: Page, spec: "AggSpec",
                 key_channels: Sequence[int]) -> Column:
    """approx_distinct over sorted groups: re-sort by (keys, bucket), fold
    registers per (group, bucket) RUN with one segment_max, then reduce
    runs per group — static shapes throughout (no [groups x m] registers
    materialized)."""
    n = page.capacity
    fn = get_aggregate("approx_distinct", spec.input_type)
    vals, elig, _ = _agg_inputs(page, spec, fn, page.row_mask())
    bucket, rho = _hll_register_inputs(vals, elig)
    operands = _sort_key_arrays(page, key_channels)
    sorted_ops = jax.lax.sort(
        operands + [bucket, rho.astype(jnp.int32),
                    elig.astype(jnp.int32)],
        num_keys=len(operands) + 1)
    live_s = ~sorted_ops[0]
    key_ops_s = sorted_ops[1:-3]
    bucket_s, rho_s, elig_s = sorted_ops[-3], sorted_ops[-2], sorted_ops[-1]
    # group ids on the sorted order
    gboundary = _boundary_scan(key_ops_s, n) & live_s
    group = jnp.cumsum(gboundary.astype(jnp.int32)) - 1
    # (group, bucket) runs
    rboundary = (gboundary |
                 (bucket_s != jnp.roll(bucket_s, 1)).at[0].set(True)) & live_s
    run = jnp.cumsum(rboundary.astype(jnp.int32)) - 1
    run_seg = jnp.where(live_s, run, n)
    reg_run = jax.ops.segment_max(jnp.where(elig_s > 0, rho_s, 0), run_seg,
                                  num_segments=n + 1)[:n]
    has_run = jax.ops.segment_max(elig_s, run_seg,
                                  num_segments=n + 1)[:n] > 0
    grp_run = jax.ops.segment_max(jnp.where(live_s, group, -1), run_seg,
                                  num_segments=n + 1)[:n]
    inv = jnp.where(has_run, jnp.exp2(-reg_run.astype(jnp.float64)), 0.0)
    grp_seg = jnp.where(has_run, grp_run, n)
    sum_present = jax.ops.segment_sum(inv, grp_seg, num_segments=n + 1)[:n]
    cnt_present = jax.ops.segment_sum(has_run.astype(jnp.float64), grp_seg,
                                      num_segments=n + 1)[:n]
    return Column(_hll_estimate(sum_present, cnt_present), None, T.BIGINT,
                  None)


def _hll_global(page: Page, spec: "AggSpec", live) -> Column:
    n = page.capacity
    fn = get_aggregate("approx_distinct", spec.input_type)
    vals, elig, _ = _agg_inputs(page, spec, fn, live)
    bucket, rho = _hll_register_inputs(vals, elig)
    seg = jnp.where(elig, bucket, _HLL_M)
    reg = jax.ops.segment_max(rho, seg, num_segments=_HLL_M + 1)[:_HLL_M]
    present = reg > 0
    sum_present = jnp.sum(
        jnp.where(present, jnp.exp2(-reg.astype(jnp.float64)), 0.0),
        keepdims=True)
    cnt_present = jnp.sum(present.astype(jnp.float64), keepdims=True)
    return Column(_hll_estimate(sum_present, cnt_present), None, T.BIGINT,
                  None)


def _percentile_grouped(page: Page, spec: "AggSpec",
                        key_channels: Sequence[int]) -> Column:
    """approx_percentile(x, p): nearest-rank pick within each sorted group
    (the qdigest role; exact at single step — error 0 <= any digest)."""
    n = page.capacity
    xcol = page.column(spec.input)
    vals, dictionary = xcol.values, xcol.dictionary
    elig = page.row_mask() & xcol.valid_mask()
    if spec.mask_channel is not None:
        fcol = page.column(spec.mask_channel)
        elig = elig & fcol.values & fcol.valid_mask()
    sort_vals = _nan_as_largest(vals) if jnp.issubdtype(
        vals.dtype, jnp.floating) else vals
    operands = _sort_key_arrays(page, key_channels)
    perm = jnp.arange(n, dtype=jnp.int32)
    sorted_ops = jax.lax.sort(
        operands + [(~elig), sort_vals, perm],
        num_keys=len(operands) + 2)
    live_s = ~sorted_ops[0]
    key_ops_s = sorted_ops[1:-3]
    elig_s = ~sorted_ops[-3]
    perm_s = sorted_ops[-1]
    gboundary = _boundary_scan(key_ops_s, n) & live_s
    group = jnp.cumsum(gboundary.astype(jnp.int32)) - 1
    seg = jnp.where(live_s, group, n)
    pos = jnp.arange(n, dtype=jnp.int32)
    start = jax.ops.segment_min(jnp.where(live_s, pos, n), seg,
                                num_segments=n + 1)[:n]
    cnt = jax.ops.segment_sum(elig_s.astype(jnp.int32), seg,
                              num_segments=n + 1)[:n]
    pcol = page.column(spec.input2)
    p_sorted = jnp.take(pcol.values, perm_s, mode="clip") \
        .astype(jnp.float64)
    p_g = jax.ops.segment_max(jnp.where(live_s, p_sorted, -jnp.inf), seg,
                              num_segments=n + 1)[:n]
    k = jnp.clip(jnp.ceil(p_g * cnt.astype(jnp.float64)).astype(jnp.int32),
                 1, jnp.maximum(cnt, 1))
    idx = jnp.clip(start + k - 1, 0, n - 1)
    vals_s = jnp.take(vals, perm_s, mode="clip")
    out_vals = jnp.take(vals_s, idx, mode="clip")
    return Column(out_vals, cnt > 0, xcol.type, dictionary)


def _percentile_global(page: Page, spec: "AggSpec", live) -> Column:
    n = page.capacity
    xcol = page.column(spec.input)
    vals, dictionary = xcol.values, xcol.dictionary
    elig = live & xcol.valid_mask()
    if spec.mask_channel is not None:
        fcol = page.column(spec.mask_channel)
        elig = elig & fcol.values & fcol.valid_mask()
    sort_vals = _nan_as_largest(vals) if jnp.issubdtype(
        vals.dtype, jnp.floating) else vals
    perm = jnp.arange(n, dtype=jnp.int32)
    sorted_ops = jax.lax.sort([(~elig), sort_vals, perm], num_keys=2)
    elig_s = ~sorted_ops[0]
    perm_s = sorted_ops[-1]
    cnt = jnp.sum(elig_s.astype(jnp.int32))
    pcol = page.column(spec.input2)
    p = jnp.max(jnp.where(live, pcol.values.astype(jnp.float64), -jnp.inf))
    k = jnp.clip(jnp.ceil(p * cnt.astype(jnp.float64)).astype(jnp.int32),
                 1, jnp.maximum(cnt, 1))
    idx = jnp.clip(k - 1, 0, n - 1)
    vals_s = jnp.take(vals, perm_s, mode="clip")
    out_vals = jnp.take(vals_s, idx[None], mode="clip")
    return Column(out_vals, (cnt > 0)[None], xcol.type, dictionary)


def _geomean_state_factory(in_type):
    def state(t):
        return (
            StateColumn(T.DOUBLE,
                        lambda v, m: jnp.where(
                            m, jnp.log(_to_double(v, in_type)), 0.0),
                        "sum"),
            StateColumn(T.BIGINT, lambda v, m: m.astype(jnp.int64), "sum"),
        )
    return state


def _geomean_final(state, _):
    s, n = state
    return jnp.exp(s / jnp.maximum(n.astype(jnp.float64), 1.0)), n > 0


# aggregates resolved by picking one row per group rather than reducing
# independent state columns (reference: MinMaxByNStateFactory / the min_by
# codegen path); these never split into PARTIAL/FINAL across an exchange
POSITIONAL_AGGREGATES = frozenset({"min_by", "max_by", "arbitrary"})

# moment aggregates computed with CENTERED sums (two passes over the sorted
# segments: means first, then squared deviations) for numerical stability —
# the naive E[x²]−E[x]² raw-moment form catastrophically cancels for large-
# mean data. Centered sums have no column-wise commutative merge, so these
# are single-step only (Trino instead merges central moments with Chan's
# update; reference operator/aggregation/state/CentralMomentsState.java —
# a future optimization would add a custom merge path to the FINAL step).
CENTERED_AGGREGATES = frozenset({
    "variance", "var_samp", "var_pop", "stddev", "stddev_samp", "stddev_pop",
    "corr", "covar_pop", "covar_samp", "regr_slope", "regr_intercept"})

# sketch aggregates with their own sorted evaluation (HyperLogLog register
# folding / rank selection) — single-step like DISTINCT: the whole group's
# rows must be colocated in one kernel call
SKETCH_AGGREGATES = frozenset({"approx_distinct", "approx_percentile"})

# collectors packing group elements into the list layout (ArrayBlock /
# MapBlock output) — single-step, and the executor pre-computes the
# static element capacity (list_len) from the collected page
COLLECT_AGGREGATES = frozenset({"array_agg", "histogram", "map_agg"})

# aggregates that must see every row of a group in ONE kernel invocation
SINGLE_STEP_AGGREGATES = (POSITIONAL_AGGREGATES | CENTERED_AGGREGATES
                          | SKETCH_AGGREGATES | COLLECT_AGGREGATES)


def get_aggregate(name: str, in_type: Optional[T.Type]) -> AggregateFunction:
    """Resolve an aggregate by name + input type (FunctionRegistry analog).

    For two-argument aggregates `in_type` is a tuple (first, second) of the
    argument types.
    """
    n = name.lower()
    tx, ty = (in_type if isinstance(in_type, tuple) else (in_type, None))
    if n == "count":
        return AggregateFunction("count", _count_state, _count_final,
                                 lambda t: T.BIGINT)
    if n == "count_if":
        return AggregateFunction("count_if", _count_if_state, _count_final,
                                 lambda t: T.BIGINT)
    if n in ("bool_and", "bool_or"):
        return AggregateFunction(
            n, lambda t: _bool_state(n == "bool_and"), _bool_final,
            lambda t: T.BOOLEAN)
    if n == "geometric_mean":
        return AggregateFunction(n, _geomean_state_factory(tx),
                                 _geomean_final, lambda t: T.DOUBLE)
    if n in CENTERED_AGGREGATES:
        # state/final unused — executed by the centered two-pass path
        return AggregateFunction(n, lambda t: (), None, lambda t: T.DOUBLE)
    if n in POSITIONAL_AGGREGATES:
        # state/final unused — executed by the positional row-selection path
        return AggregateFunction(n, lambda t: (), None, lambda t: tx)
    if n == "approx_distinct":
        return AggregateFunction(n, lambda t: (), None, lambda t: T.BIGINT)
    if n == "array_agg":
        return AggregateFunction(n, lambda t: (), None,
                                 lambda t: T.ArrayType(element=tx))
    if n == "histogram":
        return AggregateFunction(
            n, lambda t: (), None,
            lambda t: T.MapType(key=tx, value=T.BIGINT))
    if n == "map_agg":
        return AggregateFunction(
            n, lambda t: (), None, lambda t: T.MapType(key=tx, value=ty))
    if n == "approx_percentile":
        return AggregateFunction(n, lambda t: (), None, lambda t: tx)
    if n == "checksum":
        return AggregateFunction("checksum", _checksum_state,
                                 _checksum_final, lambda t: T.BIGINT)
    if n == "sum":
        out = in_type if isinstance(in_type, (T.DecimalType, T.DoubleType,
                                              T.RealType)) else T.BIGINT
        if isinstance(in_type, T.RealType):
            out = T.REAL
        return AggregateFunction("sum", _sum_state, _sum_final, lambda t: out)
    if n == "avg":
        # Trino: avg(real) -> real, avg(decimal) keeps type/scale, else double
        if isinstance(in_type, T.DecimalType):
            out = in_type
        elif isinstance(in_type, T.RealType):
            out = T.REAL
        else:
            out = T.DOUBLE
        return AggregateFunction("avg", _avg_state, _avg_final_factory(in_type),
                                 lambda t: out)
    if n == "min":
        return AggregateFunction(
            "min", lambda t: _minmax_state(t, True), _minmax_final,
            lambda t: in_type)
    if n == "max":
        return AggregateFunction(
            "max", lambda t: _minmax_state(t, False), _minmax_final,
            lambda t: in_type)
    raise KeyError(f"unknown aggregate function: {name}")


AGGREGATES = ("count", "sum", "avg", "min", "max", "count_if", "bool_and",
              "bool_or", "variance", "var_samp", "var_pop", "stddev",
              "stddev_samp", "stddev_pop", "geometric_mean", "corr",
              "covar_pop", "covar_samp", "regr_slope", "regr_intercept",
              "min_by", "max_by", "arbitrary")


@dataclasses.dataclass(frozen=True)
class AggSpec:
    """One aggregate call in a plan: fn(input_channel). input None = count(*).

    Two-argument aggregates (corr/covar/regr, min_by/max_by) carry the
    second argument in (input2, input2_type)."""

    name: str
    input: Optional[int]
    input_type: Optional[T.Type]
    mask_channel: Optional[int] = None  # e.g. count(x) FILTER (WHERE ...)
    distinct: bool = False
    input2: Optional[int] = None
    input2_type: Optional[T.Type] = None


def _sort_key_arrays(page: Page, key_channels: Sequence[int], dead=None):
    """Composite sort operands: dead-flag first, then (null, value) per key.

    Null rows' value lanes hold garbage; canonicalize them to 0 so all nulls
    of a key collate into ONE group (the null flag is a separate sort key).
    `dead` overrides the liveness flag (e.g. DISTINCT folds the aggregate's
    eligibility into it).
    """
    if dead is None:
        dead = ~page.row_mask()  # False (live) sorts before True (dead)
    operands = [dead]
    for ch in key_channels:
        col = page.column(ch)
        if col.valid is not None:
            operands.append(~col.valid)  # nulls group after non-nulls
            operands.append(jnp.where(col.valid, col.values,
                                      jnp.zeros((), col.values.dtype)))
        else:
            operands.append(col.values)
    return operands


def hash_aggregate(
    key_channels: Sequence[int],
    aggs: Sequence[AggSpec],
    step: str = Step.SINGLE,
    partial_state_channels: Optional[Sequence[Sequence[int]]] = None,
    list_len: Optional[int] = None,
) -> Callable[[Page], Page]:
    """Build a group-by aggregation operator.

    Output page layout: [key columns..., per-agg output columns...]. For
    step=PARTIAL the per-agg outputs are the raw state columns (consumed by a
    FINAL step whose partial_state_channels maps agg -> its state channels).
    Capacity: output keeps input capacity (#groups <= #rows).
    """
    key_channels = tuple(key_channels)
    for a in aggs:
        if a.distinct and (a.name in POSITIONAL_AGGREGATES
                           or (a.name in CENTERED_AGGREGATES
                               and a.input2 is not None)):
            # DISTINCT over a row-pair has no single-column first-occurrence
            # mask; refuse rather than silently dropping the qualifier
            raise NotImplementedError(f"{a.name}(DISTINCT ...)")
    if step != Step.SINGLE:
        for a in aggs:
            if a.distinct:
                # the optimizer keeps DISTINCT aggregations single-step
                # (no partial/final split across an exchange) because
                # distinctness is only decidable once a group's rows are
                # colocated; see add_exchanges' `splittable` guard.
                raise NotImplementedError(
                    f"{a.name}(DISTINCT ...) in {step} step")
            if a.name in SINGLE_STEP_AGGREGATES:
                # positional/centered state has no commutative column-wise
                # merge; the optimizer keeps these single-step
                raise NotImplementedError(f"{a.name}() in {step} step")
    resolved = [get_aggregate(a.name,
                              a.input_type if a.input2 is None
                              else (a.input_type, a.input2_type))
                for a in aggs]

    has_collect = any(a.name in COLLECT_AGGREGATES for a in aggs)

    def op(page: Page) -> Page:
        n = page.capacity
        if not key_channels:
            if has_collect:
                raise NotImplementedError(
                    "global array_agg/histogram/map_agg (no GROUP BY)")
            with op_scope("aggregate__global_reduce"):
                return _global_aggregate(page, aggs, resolved, step,
                                         partial_state_channels)
        sizes = None if has_collect else \
            _direct_key_sizes(page, key_channels, aggs)
        if sizes is not None:
            masked = _direct_reduces_masked(sizes, aggs, resolved)
            note_trace("direct_reduce_masked" if masked
                       else "direct_reduce_scattered")
            with op_scope("aggregate__direct_masked_reduce" if masked
                          else "aggregate__direct_segment_reduce"):
                return _direct_aggregate(page, key_channels, aggs, resolved,
                                         step, partial_state_channels, sizes,
                                         masked)
        return _sorted_aggregate(page, key_channels, aggs, resolved, step,
                                 partial_state_channels, list_len)

    return op


def _group_sort_operands(page: Page, key_channels: Sequence[int]):
    """`_sort_key_arrays` for the sorted GROUP BY: a dead lane's key lanes
    hold whatever the producer left there (a concatenation's overwritten
    tails, a join's clipped slots), so they are made ONE key — the dead
    lanes then sort among themselves in the order they came, and lanes
    whose live rows are a prefix in key order are in order whatever
    lies behind them."""
    operands = _sort_key_arrays(page, key_channels)
    dead = operands[0]
    return [dead] + [jnp.where(dead, jnp.zeros((), a.dtype), a)
                     for a in operands[1:]]


def _channels_read(key_channels, aggs, step, partial_state_channels):
    """channel -> whether its validity is read, for every column the
    sorted GROUP BY reads in key order: the keys, and each aggregate's
    arguments and FILTER mask or (FINAL, INTERMEDIATE) its partial states,
    whose values alone are read."""
    read = {ch: True for ch in key_channels}
    for ai, spec in enumerate(aggs):
        if step in (Step.FINAL, Step.INTERMEDIATE):
            for ch in partial_state_channels[ai]:
                read.setdefault(ch, False)
        else:
            for ch in (spec.input, spec.input2, spec.mask_channel):
                if ch is not None:
                    read[ch] = True
    return read


def _in_key_order(operands, lanes):
    """-> (in_order, `lanes` in the stable order of `operands`), `lanes`
    any tree of arrays a lane a row. The device decides: where the rows
    already lie in that order (`radix.in_order`: one compare pass) they
    are handed on as they are — no sort, no permutation, no gather —
    and where they do not, by the radix passes and one gather an array.
    (The first branch traces no op: what the device does there is the
    copies a `conditional` makes of what it returns, under the scope of
    the `cond` itself.)"""
    with op_scope("aggregate__order_test"):
        ordered = in_order(operands)
    return ordered, jax.lax.cond(
        ordered, lambda lanes: lanes,
        lambda lanes: sorted_by(operands, lanes)[0], lanes)


def _sorted_aggregate(page: Page, key_channels, aggs, resolved, step,
                      partial_state_channels, list_len) -> Page:
    """The sort-based GROUP BY: the rows in key order, a boundary where the
    key changes, every state reduced over its group's lanes by one
    `_scan_reduce`, keys and states moved to a lane a group.

    Whether the rows need ordering is decided from the data, inside the
    program (`_in_key_order`): a scan of a table stored in key order, a
    join's output in its probe's order and the concatenated outputs of
    this very operator arrive sorted, and are then neither sorted nor
    gathered. An aggregation that holds a single-step aggregate or a
    DISTINCT reads its rows through the permutation, so it always sorts.
    The lanes a dispatch ran over leave the program booked to
    `group_by_lanes_in_order` or `group_by_lanes_sorted`."""
    n = page.capacity
    indexed = any(a.distinct or a.name in SINGLE_STEP_AGGREGATES
                  for a in aggs)
    operands = _group_sort_operands(page, key_channels)
    read = {ch: page.column(ch) if valid
            else page.column(ch).with_valid(None)
            for ch, valid in _channels_read(
                key_channels, aggs, step, partial_state_channels).items()}
    perm_sorted = None
    if indexed:
        with op_scope("aggregate__group_sort"):
            (dead_sorted, read), perm_sorted = sorted_by(
                operands, (operands[0], read))
        note_device("group_by_lanes_sorted", n)
    else:
        with op_scope("aggregate__group_sort"):
            ordered, (dead_sorted, read) = _in_key_order(
                operands, (operands[0], read))
        lanes_in_order = jnp.where(ordered, n, 0).astype(jnp.int32)
        note_device("group_by_lanes_in_order", lanes_in_order)
        note_device("group_by_lanes_sorted", n - lanes_in_order)
    # the page in key order: the columns nobody reads stay where they were
    sorted_page = Page(tuple(read.get(ch, col)
                             for ch, col in enumerate(page.columns)),
                       page.num_rows)
    with op_scope("aggregate__group_bounds"):
        # boundary detection on the *sorted* key operands (incl. null
        # flags)
        live_sorted = ~dead_sorted
        boundary = _boundary_scan(
            _sort_key_arrays(sorted_page, key_channels, dead_sorted)[1:],
            n) & live_sorted
        # who moves where, once, for the keys and for every state
        takes, num_groups = shift_takes(boundary)
        is_group = jnp.arange(n, dtype=jnp.int32) < num_groups
        seg = None
        if indexed:
            # route dead rows to an out-of-range segment id so they drop
            # out
            seg = jnp.where(live_sorted,
                            jnp.cumsum(boundary.astype(jnp.int32)) - 1, n)

    def to_group_lanes(a):
        # a group's first lane to the group's lane, as the states go; the
        # lanes past the groups read the first sorted row's
        moved, _ = shift_move(a, takes, n, tag="key_move")
        return jnp.where(is_group.reshape((n,) + (1,) * (a.ndim - 1)),
                         moved, a[:1])
    with op_scope("aggregate__key_move"):
        out_cols: List[Column] = [
            jax.tree_util.tree_map(to_group_lanes, sorted_page.column(ch))
            for ch in key_channels]

    with op_scope("aggregate__segment_reduce"):
        agg_cols = _accumulate(page, sorted_page, aggs, resolved, step,
                               partial_state_channels, perm_sorted, seg,
                               boundary, live_sorted, takes, is_group,
                               key_channels, list_len)
    out_cols.extend(agg_cols)
    return Page(tuple(out_cols), num_groups)


def _agg_inputs(page: Page, spec: "AggSpec", fn, base_mask, gather=None):
    """Per-row (vals, mask, dictionary) for one aggregate — input column,
    second argument, argument nullness and FILTER mask folded in. The ONE
    definition shared by the sorted, global and direct aggregation paths
    (semantics must not depend on which path the group keys select).
    `gather` reorders row-space arrays (e.g. through a sort permutation)."""
    def g(a):
        return a if gather is None else jnp.take(a, gather, mode="clip")
    dictionary = None
    if spec.input is not None:
        col = page.column(spec.input)
        dictionary = col.dictionary
        vals = g(col.values)
        mask = base_mask & g(col.valid_mask())
    else:
        vals = jnp.zeros(page.capacity, dtype=jnp.int64)
        mask = base_mask
    if spec.input2 is not None:
        col2 = page.column(spec.input2)
        mask = mask & g(col2.valid_mask())
        vals = (vals, g(col2.values))
    if spec.mask_channel is not None:
        fcol = page.column(spec.mask_channel)
        mask = mask & g(fcol.values & fcol.valid_mask())
    return vals, mask, dictionary


def _final_state_contribs(page: Page, states, chans, live_mask):
    """FINAL-step per-state (contribution, reducer): partial state columns
    with dead rows replaced by each reducer's identity — shared by the
    sorted, global and direct paths."""
    out = []
    for sc, ch in zip(states, chans):
        vals = page.column(ch).values
        if sc.reducer == "sum":
            ident = jnp.zeros((), dtype=vals.dtype)
        else:
            ident = _ident_for(vals.dtype, sc.reducer == "min")
        out.append((jnp.where(live_mask, vals, ident), sc.reducer))
    return out


_DIRECT_MAX_GROUPS = 4096
# slots x state columns up to which the direct path reduces lane-wise under
# slot masks; above it the scatter runs. On a v5e at 1 048 576 lanes of
# int64 the masked form costs 0.4 ms + 2.6-3.6 us a slot and state, the
# scatter 72-91 ms whatever the table: 15 360 (1 024 slots x 15 states) is
# 39 ms against 90, 61 440 is 159 against 88 (PERF.md section 6, PR 29). The
# power of two under the last measured win, on the safe side of a crossover
# that a line through those points puts near 34 000
_MASKED_MAX_SLOT_STATES = 16384


def _direct_key_sizes(page: Page, key_channels, aggs):
    """Static per-key code-space sizes when EVERY group key is
    dictionary-encoded and the combined key space is small — the
    BigintGroupByHash / dictionary-aware fast path (reference:
    operator/GroupByHash.java dictionary mode). Returns None when the
    sort-based general path must run."""
    for a in aggs:
        if a.distinct or a.name in SINGLE_STEP_AGGREGATES:
            return None
    sizes = []
    total = 1
    for ch in key_channels:
        col = page.column(ch)
        if col.dictionary is None:
            return None
        sizes.append(len(col.dictionary) + 1)   # +1: the NULL slot
        total *= sizes[-1]
    if total > _DIRECT_MAX_GROUPS:
        return None
    return tuple(sizes)


def partial_states_are_few(page: Page, key_channels, aggs) -> bool:
    """Whether the PARTIAL aggregate of a page shaped like `page` is a
    small static state — one row (no GROUP BY), or direct-address slots
    (`_direct_key_sizes`) — so that many pages' states may be stacked in
    one program and merged by concatenation. From shapes and dictionaries
    alone: `page` may be what `jax.eval_shape` gives for the chain that
    feeds the aggregate. The executors that walk a scan inside its program
    ask (exec/mesh_exec, exec/local_planner.compose_walk)."""
    if any(a.name in COLLECT_AGGREGATES for a in aggs):
        return False
    return not key_channels or \
        _direct_key_sizes(page, key_channels, aggs) is not None


def _direct_reduces_masked(sizes, aggs, resolved) -> bool:
    """Which form the direct path's reduce takes, from what a trace can
    see alone: the slot count and the number of state columns."""
    states = sum(len(fn.state(spec.input_type))
                 for spec, fn in zip(aggs, resolved))
    return math.prod(sizes) * states <= _MASKED_MAX_SLOT_STATES


def _masked_reduce(contrib, hit, reducer):
    """Per-slot reduction of `contrib` [n] under the slot masks `hit`
    [nseg, n]: a compare-select-reduce along the lanes that XLA fuses into
    one pass per state, the [nseg, n] operand never materialised. The lane
    axis stays minor — a minor axis of nseg slots or of k stacked states
    would be padded to 128 lanes."""
    if reducer == "sum":
        return jnp.sum(jnp.where(hit, contrib[None, :],
                                 jnp.zeros((), contrib.dtype)),
                       axis=1, dtype=contrib.dtype)
    ident = _ident_for(contrib.dtype, reducer == "min")
    red = jnp.min if reducer == "min" else jnp.max
    return red(jnp.where(hit, contrib[None, :], ident), axis=1,
               initial=ident)


def _direct_aggregate(page: Page, key_channels, aggs, resolved, step,
                      partial_state_channels, sizes, masked) -> Page:
    """Group-by over a small static key space WITHOUT sorting: segment ids
    are computed arithmetically from dictionary codes, each state reduces
    into a static table of `nseg` slots, and present groups compact to a
    tiny output page.

    The reduce takes one of two forms (`_direct_reduces_masked`). A small
    table reduces each state lane-wise under one mask per slot
    (`_masked_reduce`): nseg x states select-and-add passes over the page,
    1.4 ms for q1's 12 slots x 15 states over a 1 048 576-lane page on a
    v5e. A larger one scatters with jax.ops.segment_*: its cost does not
    grow with nseg, but every lane collides and the batched [n, k] operand
    is tiled to 128 columns, 1 GB of temporaries — 90 ms for the same q1
    page, 72 ms for a single state."""
    n = page.capacity
    live = page.row_mask()
    nseg = math.prod(sizes)
    # combined code; NULL key -> last slot of its key's code space
    combined = jnp.zeros(n, dtype=jnp.int32)
    stride = nseg
    strides = []
    for ch, size in zip(key_channels, sizes):
        stride //= size
        strides.append(stride)
        col = page.column(ch)
        code = jnp.clip(col.values.astype(jnp.int32), 0, size - 2)
        if col.valid is not None:
            code = jnp.where(col.valid, code, size - 1)
        combined = combined + code * stride
    seg = jnp.where(live, combined, nseg)       # dead rows drop out
    n_out = nseg + 1

    if masked:
        # [nseg, n], lanes minor; a dead row's slot nseg matches none
        hit = seg[None, :] == jnp.arange(nseg, dtype=jnp.int32)[:, None]
        present = jnp.any(hit, axis=1)
    else:
        cnt_live = jax.ops.segment_sum(live.astype(jnp.int32), seg,
                                       num_segments=n_out)[:nseg]
        present = cnt_live > 0
    num_groups = jnp.sum(present).astype(jnp.int32)
    pos = jnp.cumsum(present.astype(jnp.int32)) - 1
    scatter_idx = jnp.where(present, pos, nseg)

    def compact(values_per_slot, valid_per_slot=None):
        out_v = jnp.zeros((nseg,), dtype=values_per_slot.dtype).at[
            scatter_idx].set(values_per_slot, mode="drop")
        if valid_per_slot is None:
            return out_v, None
        out_m = jnp.zeros((nseg,), dtype=jnp.bool_).at[scatter_idx].set(
            valid_per_slot, mode="drop")
        return out_v, out_m

    out_cols: List[Column] = []
    slot = jnp.arange(nseg, dtype=jnp.int32)
    for ch, size, stride in zip(key_channels, sizes, strides):
        col = page.column(ch)
        code = (slot // stride) % size
        is_null = code == size - 1
        v, m = compact(code.astype(col.values.dtype),
                       ~is_null if col.valid is not None else None)
        out_cols.append(Column(v, m, col.type, col.dictionary))

    # first collect EVERY state's contribution array, then reduce
    pending: List[dict] = []
    for ai, (spec, fn) in enumerate(zip(aggs, resolved)):
        states = fn.state(spec.input_type)
        entry = {"states": states, "contribs": []}
        if step in (Step.FINAL, Step.INTERMEDIATE):
            chans = partial_state_channels[ai]
            entry["dictionary"] = page.column(chans[0]).dictionary
            entry["contribs"] = _final_state_contribs(page, states, chans,
                                                      live)
        else:
            vals, mask, dictionary = _agg_inputs(page, spec, fn, live)
            entry["dictionary"] = dictionary
            for sc in states:
                entry["contribs"].append((sc.contrib(vals, mask),
                                          sc.reducer))
        pending.append(entry)

    if masked:
        # nothing stacked: identical contributions (the non-null counts of
        # columns with no valid mask are all `live`) are XLA's CSE to fold
        def reduced(contrib, reducer):
            return _masked_reduce(contrib, hit, reducer)
    else:
        reduced = _scatter_reducer(pending, seg, nseg)

    for (spec, fn), entry in zip(zip(aggs, resolved), pending):
        state_arrays = [reduced(c, r) for c, r in entry["contribs"]]
        states = entry["states"]
        dictionary = entry["dictionary"]
        if step in (Step.PARTIAL, Step.INTERMEDIATE):
            for sc, arr in zip(states, state_arrays):
                d = dictionary if T.is_string(sc.type) else None
                v, _ = compact(arr.astype(sc.type.dtype))
                out_cols.append(Column(v, None, sc.type, d))
        else:
            values, valid = fn.final(state_arrays, None)
            v, m = compact(values, valid)
            out_cols.append(_agg_out_column(fn, spec, v, m, dictionary))
    return Page(tuple(out_cols), num_groups)


def _scatter_reducer(pending, seg, nseg):
    """The scatter form of the direct reduce: all "sum" states of one
    dtype in ONE batched segment_sum ([n, k] data) — a scatter's per-call
    cost dominates, so the sum states share one — and a segment_min/max
    per other state."""
    n_out = nseg + 1
    sum_batches: dict = {}       # dtype -> list of contrib arrays
    sum_slots: dict = {}         # id(contrib) -> (dtype, index)
    for entry in pending:
        for contrib, reducer in entry["contribs"]:
            if reducer == "sum":
                lst = sum_batches.setdefault(contrib.dtype, [])
                sum_slots[id(contrib)] = (contrib.dtype, len(lst))
                lst.append(contrib)
    sum_results = {
        dt: jax.ops.segment_sum(jnp.stack(lst, axis=1), seg,
                                num_segments=n_out)[:nseg]
        for dt, lst in sum_batches.items()}

    def reduced(contrib, reducer):
        if reducer == "sum":
            dt, j = sum_slots[id(contrib)]
            return sum_results[dt][:, j]
        return _segment_reduce(contrib, seg, n_out, reducer)[:nseg]
    return reduced


def _boundary_scan(key_ops, n) -> jnp.ndarray:
    """Group-start flags over lexicographically sorted key arrays.

    NaN is ONE value for grouping/DISTINCT purposes (SQL/Trino semantics),
    so adjacent NaNs do NOT open a new group despite NaN != NaN.
    """
    boundary = jnp.zeros(n, dtype=jnp.bool_)
    for arr in key_ops:
        prev = jnp.roll(arr, 1)
        ne = arr != prev
        if jnp.issubdtype(arr.dtype, jnp.floating):
            ne = ne & ~(jnp.isnan(arr) & jnp.isnan(prev))
        boundary = boundary | ne
    return boundary.at[0].set(True)


def _nan_as_largest(v: jnp.ndarray) -> jnp.ndarray:
    """Canonicalize NaN to +inf: ORDER BY / min_by / max_by treat NaN as the
    largest value (Trino's totalOrder comparison)."""
    if jnp.issubdtype(v.dtype, jnp.floating):
        return jnp.where(jnp.isnan(v), jnp.asarray(jnp.inf, v.dtype), v)
    return v


def _segment_reduce(contrib, seg, n, reducer):
    if reducer == "sum":
        return jax.ops.segment_sum(contrib, seg, num_segments=n)
    if reducer == "min":
        return jax.ops.segment_min(contrib, seg, num_segments=n)
    if reducer == "max":
        return jax.ops.segment_max(contrib, seg, num_segments=n)
    raise ValueError(reducer)


def _distinct_first_mask(page: Page, key_channels: Sequence[int],
                         spec: "AggSpec") -> jnp.ndarray:
    """Row-order mask marking the first eligible row of each
    (group keys, argument value) pair — the MarkDistinctOperator.java:38
    analog, phrased as one extra lexicographic sort + boundary scan so
    DISTINCT costs O(n log n) on the VPU instead of a hash table.

    Eligibility folds in liveness, argument non-nullness (DISTINCT
    aggregates skip NULL inputs) and the aggregate's FILTER mask, so
    distinctness is computed over exactly the rows the aggregate sees.
    """
    n = page.capacity
    col = page.column(spec.input)
    eligible = page.row_mask() & col.valid_mask()
    if spec.mask_channel is not None:
        fcol = page.column(spec.mask_channel)
        eligible = eligible & fcol.values & fcol.valid_mask()
    # the argument is just one more sort key after the group keys
    operands = _sort_key_arrays(page, tuple(key_channels) + (spec.input,),
                                dead=~eligible)
    perm = jnp.arange(n, dtype=jnp.int32)
    sorted_ops = jax.lax.sort(operands + [perm], num_keys=len(operands))
    perm_s = sorted_ops[-1]
    elig_s = ~sorted_ops[0]
    first = _boundary_scan(sorted_ops[1:-1], n) & elig_s
    return jnp.zeros(n, dtype=jnp.bool_).at[perm_s].set(first)


_SCAN_OPS = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


def _scan_reduce(contribs, boundary, live_sorted, takes, is_group):
    """Per-group reduction of every `(contribution, reducer)` of `contribs`
    over SORTED lanes: `boundary` flags a group's first lane, the live
    lanes are a prefix and a dead lane contributes its reducer's identity.
    Group g's state lands on lane g — `jax.ops.segment_*(contrib, seg,
    num_segments=n)`'s layout, beside the keys `aggregate__key_gather`
    places — with no index: no scatter, no gather, no sort.

    1. A segmented scan from the right. Lane i is `open` in round k while
       no group ends in [i, i + 2^k): it then holds the reduction of
       exactly those lanes, and takes `op(own, lane i + 2^k)`, which holds
       the next 2^k lanes or as many as the group has left. log2(n) rounds
       of a select between an array and itself shifted by a static power
       of two; afterwards a group's FIRST lane holds its state. Which lane
       is open when depends on the flags alone, so their rounds run once
       and are kept as the bits of one int32 a lane; every kind of state
       (dtype x reducer, its states end to end in one array) then scans
       alone, a round at a time (the barriers, as in `Page.filter`: the
       scheduler holds two copies of one array, not of every state).
    2. One order-preserving compaction of the scanned arrays under
       `boundary` by `Page.filter`'s shift-and-select rounds
       (`page.shift_takes`, `page.shift_move`): who takes when is worked
       out once, and every state of every aggregate moves under it.

    Who takes when in the compaction (`takes`, `is_group`) is the
    caller's, which moves the keys under it too.

    Lanes at and past the group count are set to what the scatter left
    there (0 for a sum, the identity for min/max), so every consumer —
    `fn.final`, `_agg_out_column`, the INTERMEDIATE step, the pass-through
    partial's mixing buffer, the HAVING chain step — reads what it read.
    Integer and decimal sums are the same int64 additions in another
    order (exact, wrapping alike), min/max are order-free, a double sum
    runs in a fixed tree order where the scatter's was undefined.

    On a v5e (PERF.md section 6, PR 40, step 0; Q18's inner GROUP BY, two
    int64 states, fenced): 1 048 576 lanes take 1.98 ms (1.14 in a chain;
    6.6 s to compile, 26.5 MB of temporaries) against 143.8 ms for the two
    `segment_sum`s (0.3 s, none); 16 777 216 lanes 127.1 ms (8.2 s,
    746 MB) against 4 143 ms (0.3 s, 134 MB). Without the rounds'
    barriers 2.0 and 124.3 ms, and 1 288 MB against 762 in the whole
    FINAL program. Roads not taken: running sums and one gather at the
    groups' last lanes (integers only) 87.7 and 1 553 ms — the gather and
    the scatter of the last lanes' positions cost by the index;
    `jax.lax.associative_scan` 8.07 ms at 1 048 576 lanes after 75 s of
    compile, and not compiled in 85 minutes at 16 777 216. The whole
    sorted aggregate: a PARTIAL page 266.6 -> 125.0 ms, FINAL at 2^24
    6 481 -> 2 531 ms; what was left was gathers through the sort
    permutation (the keys', the inputs', the radix passes').

    Since PR 45 (PERF.md section 6, step 0; same chip, whole programs,
    fenced) lanes that arrive in key order are handed to these rounds as
    they are, and the keys are moved as the states are: the PARTIAL page
    124.8 -> 2.45 ms (1.38 in a chain), FINAL at 2^24 2 530 -> 164.6 ms,
    Q13's count over 2^24 joined lanes 2 528 -> 100.7 ms; the same lanes
    shuffled, which take the sort and one gather an array, 123.1 -> 68.2,
    2 276 -> 1 615 and 2 015 -> 1 164 ms.
    """
    n = boundary.shape[0]
    # every input's gather through the sort permutation is done before a
    # round starts: the TPU compiler gives a gather one of two forms by
    # where its table lives, and with the rounds' arrays wanting the same
    # fast memory at the same time the tables were moved out and every
    # gather of the program took three times as long (a Q18 page 201.8 ms
    # against 125.0 with this barrier, 2^24 lanes 3 914 against 2 531)
    gathered, boundary, live_sorted = jax.lax.optimization_barrier(
        ([v for v, _ in contribs], boundary, live_sorted))
    contribs = [(v, reducer)
                for v, (_, reducer) in zip(gathered, contribs)]
    # lane i + 1 continues lane i's group; the last lane's group ends there
    open_ = shift_left(~boundary & live_sorted, 1)
    opens = jnp.zeros(n, dtype=jnp.int32)
    s = 1
    while s < n:
        opens = opens | jnp.where(open_, s, 0)
        open_ = open_ & shift_left(open_, s)
        open_, opens = jax.lax.optimization_barrier((open_, opens))
        s *= 2
    # states that reduce alike ride ONE array, end to end, each under the
    # same flags: the program's size (and its compile time, which grew
    # faster than the states) follows the kinds of state, not their number.
    # No group runs from one state's lanes into the next's: lane n - 1 is
    # never open, and no lane takes from beyond lane n - 1
    alike: dict = {}
    for i, (v, reducer) in enumerate(contribs):
        alike.setdefault((v.dtype, reducer), []).append(i)
    out: list = [None] * len(contribs)
    for (dtype, reducer), members in alike.items():
        k = len(members)
        v = jnp.concatenate([contribs[i][0] for i in members])
        flags = jnp.tile(opens, k)
        op = _SCAN_OPS[reducer]
        s = 1
        while s < n:
            # an open lane's partner lies inside its array: what the shift
            # fills in is never read
            v = jnp.where((flags & s) != 0, op(v, shift_left(v, s)), v)
            v, flags = jax.lax.optimization_barrier((v, flags))
            s *= 2
        v, _ = shift_move(v, jnp.tile(takes, k), n)
        rest = jnp.zeros((), dtype) if reducer == "sum" \
            else _ident_for(dtype, reducer == "min")
        for j, i in enumerate(members):
            out[i] = jnp.where(is_group, v[j * n:(j + 1) * n], rest)
    return out


def _accumulate(page, sorted_page, aggs, resolved, step,
                partial_state_channels, perm_sorted, seg, boundary,
                live_sorted, takes, is_group, key_channels=(),
                list_len=None) -> List[Column]:
    """Per-agg state accumulation + (for FINAL/SINGLE) final projection.
    Every state column of every aggregate that reduces with sum/min/max
    reads its rows from `sorted_page`, in key order, and goes through ONE
    `_scan_reduce` (its docstring has the chip's timings of this form and
    of the scatter it replaced); the single-step aggregates keep their
    own evaluation over `page`, `perm_sorted` and `seg`."""
    n = page.capacity
    dmask_cache: dict = {}

    def distinct_mask(spec):
        # multiple DISTINCT aggs over one argument share the sort+mask
        key = (spec.input, spec.mask_channel)
        if key not in dmask_cache:
            dmask_cache[key] = jnp.take(
                _distinct_first_mask(page, key_channels, spec), perm_sorted,
                mode="clip")
        return dmask_cache[key]

    # first every aggregate's contributions (or, single-step, its finished
    # column), then one reduce for all of them, then the projections
    entries: list = []
    for ai, (spec, fn) in enumerate(zip(aggs, resolved)):
        if step in (Step.FINAL, Step.INTERMEDIATE):
            # inputs are partial state columns; merge with each state's
            # reducer (dead rows contribute the reducer identity)
            chans = partial_state_channels[ai]
            states = fn.state(spec.input_type)
            entries.append((states, page.column(chans[0]).dictionary,
                            _final_state_contribs(sorted_page, states, chans,
                                                  live_sorted)))
        elif spec.name in COLLECT_AGGREGATES:
            entries.append(_collect_grouped(page, spec, fn, perm_sorted, seg,
                                            n, list_len))
        elif spec.name == "approx_distinct":
            entries.append(_hll_grouped(page, spec, key_channels))
        elif spec.name == "approx_percentile":
            entries.append(_percentile_grouped(page, spec, key_channels))
        elif spec.name in POSITIONAL_AGGREGATES:
            entries.append(_positional_grouped(page, spec, perm_sorted, seg,
                                               n))
        elif spec.name in CENTERED_AGGREGATES:
            extra = distinct_mask(spec) if spec.distinct else None
            entries.append(_centered_grouped(page, spec, perm_sorted, seg, n,
                                             extra))
        else:
            states = fn.state(spec.input_type)
            vals, mask, dictionary = _agg_inputs(sorted_page, spec, fn,
                                                 live_sorted)
            if spec.distinct:
                mask = mask & distinct_mask(spec)
            entries.append((states, dictionary,
                            [(sc.contrib(vals, mask), sc.reducer)
                             for sc in states]))

    contribs = [c for e in entries if isinstance(e, tuple) for c in e[2]]
    reduced = iter(())
    if contribs:
        note_trace(f"sorted_reduce_scan:{n}")
        reduced = iter(_scan_reduce(contribs, boundary, live_sorted, takes,
                                    is_group))

    out: List[Column] = []
    for (spec, fn), entry in zip(zip(aggs, resolved), entries):
        if isinstance(entry, Column):
            out.append(entry)
            continue
        states, dictionary, _ = entry
        state_arrays = [next(reduced) for _ in states]
        if step in (Step.PARTIAL, Step.INTERMEDIATE):
            for sc, arr in zip(states, state_arrays):
                d = dictionary if T.is_string(sc.type) else None
                out.append(Column(arr.astype(sc.type.dtype), None, sc.type,
                                  d))
        else:   # FINAL, SINGLE
            values, valid = fn.final(state_arrays, None)
            out.append(_agg_out_column(fn, spec, values, valid, dictionary))
    return out


def passthrough_partial(key_channels: Sequence[int],
                        aggs: Sequence["AggSpec"]):
    """BYPASS-mode partial aggregation ("Partial Partial Aggregates"
    full bypass): emit ONE PARTIAL-layout state row per INPUT row — key
    columns pass through untouched, each aggregate's state columns are
    its per-row contributions — with no sort and no segment reduction.
    O(n) map instead of O(n log n) sort: when observed NDV ~ rows the
    sort collapses nothing, so the adaptive executor routes pages here
    and lets the per-partition finalize (Step.INTERMEDIATE/FINAL over
    spilled hash partitions) do ALL the grouping once.

    Output is layout-identical to Step.PARTIAL, so pass-through pages,
    real partial pages, and compacted intermediate pages mix freely in
    one buffer/store."""
    key_channels = tuple(key_channels)
    for a in aggs:
        if a.distinct or a.name in SINGLE_STEP_AGGREGATES:
            # same restriction as PARTIAL: these need a whole group in
            # one kernel call (the executor routes them elsewhere)
            raise NotImplementedError(f"{a.name}() in bypass partial")
    resolved = [get_aggregate(a.name,
                              a.input_type if a.input2 is None
                              else (a.input_type, a.input2_type))
                for a in aggs]

    def op(page: Page) -> Page:
        live = page.row_mask()
        out_cols: List[Column] = [page.column(ch) for ch in key_channels]
        with op_scope("aggregate__passthrough_states"):
            for spec, fn in zip(aggs, resolved):
                states = fn.state(spec.input_type)
                vals, mask, dictionary = _agg_inputs(page, spec, fn, live)
                for sc in states:
                    d = dictionary if T.is_string(sc.type) else None
                    out_cols.append(Column(
                        sc.contrib(vals, mask).astype(sc.type.dtype), None,
                        sc.type, d))
        return Page(tuple(out_cols), page.num_rows)

    return op


def group_max_size(key_channels: Sequence[int]):
    """Max live group size — the executor's sizing pre-pass for collect
    aggregates (one scalar fetch buys the static element capacity)."""
    key_channels = tuple(key_channels)

    def op(page: Page):
        n = page.capacity
        operands = _sort_key_arrays(page, key_channels)
        sorted_ops = jax.lax.sort(operands, num_keys=len(operands))
        live = ~sorted_ops[0]
        boundary = _boundary_scan(sorted_ops[1:], n) & live
        seg = jnp.where(live,
                        jnp.cumsum(boundary.astype(jnp.int32)) - 1, n)
        counts = jax.ops.segment_sum(live.astype(jnp.int32), seg,
                                     num_segments=n + 1)[:n]
        return jnp.max(counts)
    return op


def _collect_grouped(page: Page, spec: "AggSpec", fn, perm_sorted, seg,
                     n, list_len) -> Column:
    """array_agg / histogram / map_agg over sorted segments, packing each
    group's elements into the list layout (values [groups_cap, L] +
    lengths). L (`list_len`) is the executor-provided static element
    capacity (max group size fetched from the collected page — the
    data-dependent-shape escape hatch every blocking collector needs).
    NULL inputs are skipped (documented deviation from Trino's
    array_agg, which keeps them)."""
    if list_len is None:
        raise ValueError("collect aggregates need list_len")
    L = int(list_len)
    out_type = fn.output_type(None)
    idx = jnp.arange(n, dtype=jnp.int32)
    vals, mask, dictionary = _agg_inputs(page, spec, fn, seg < n,
                                         gather=perm_sorted)
    if spec.input2 is not None:
        vals, vals2 = vals
    else:
        vals2 = None
    if spec.name == "array_agg":
        elig = mask
        excl = jnp.cumsum(elig.astype(jnp.int32)) - elig.astype(jnp.int32)
        boundary = jnp.concatenate(
            [jnp.ones(1, jnp.bool_), seg[1:] != seg[:-1]])
        run_start = jax.lax.cummax(jnp.where(boundary, idx, 0))
        within = excl - jnp.take(excl, run_start, mode="clip")
        ok = elig & (within < L)
        srow = jnp.where(ok, seg, n)
        plane = jnp.zeros((n, L), dtype=vals.dtype).at[
            srow, jnp.clip(within, 0, L - 1)].set(vals, mode="drop")
        lengths = jnp.minimum(
            jax.ops.segment_sum(elig.astype(jnp.int32), seg,
                                num_segments=n + 1)[:n], L)
        return Column(plane, None, out_type, dictionary,
                      lengths=lengths.astype(jnp.int32))
    # histogram / map_agg: re-sort by (group segment, key value) so each
    # distinct key forms a run; pack one entry per run
    kv = _nan_as_largest(vals)
    segk = jnp.where(mask, seg, n)
    seg_s, kv_s, rows_s = jax.lax.sort([segk, kv, idx], num_keys=2)
    live = seg_s < n
    gb = jnp.concatenate([jnp.ones(1, jnp.bool_), seg_s[1:] != seg_s[:-1]])
    pb = (gb | jnp.concatenate(
        [jnp.ones(1, jnp.bool_), kv_s[1:] != kv_s[:-1]])) & live
    pair_id = jnp.cumsum(pb.astype(jnp.int32)) - 1
    pair_of_row = jnp.where(live, pair_id, n)
    g_start = jax.lax.cummax(jnp.where(gb, idx, 0))
    ordinal = pair_id - jnp.take(pair_id, g_start, mode="clip")
    first = pb & (ordinal < L)
    srow = jnp.where(first, seg_s, n)
    scol = jnp.clip(ordinal, 0, L - 1)
    keys_plane = jnp.zeros((n, L), dtype=kv_s.dtype).at[
        srow, scol].set(kv_s, mode="drop")
    aux_dict = None
    if spec.name == "histogram":
        counts = jax.ops.segment_sum(live.astype(jnp.int64), pair_of_row,
                                     num_segments=n + 1)[:n]
        aux_vals = jnp.take(counts, jnp.clip(pair_id, 0, n - 1),
                            mode="clip")
        aux_dtype = jnp.int64
    else:  # map_agg: first value seen for each key wins
        # vals2 is in the group-sort row order; re-order through the
        # secondary (group, key) sort's permutation
        aux_vals = jnp.take(vals2, rows_s, mode="clip")
        aux_dtype = vals2.dtype
        if spec.input2 is not None:
            aux_dict = page.column(spec.input2).dictionary
    aux_plane = jnp.zeros((n, L), dtype=aux_dtype).at[
        srow, scol].set(aux_vals.astype(aux_dtype), mode="drop")
    lengths = jnp.minimum(
        jax.ops.segment_sum(pb.astype(jnp.int32), seg_s,
                            num_segments=n + 1)[:n], L)
    return Column(keys_plane, None, out_type, dictionary,
                  lengths=lengths.astype(jnp.int32), aux=aux_plane,
                  aux_dictionary=aux_dict)


def _positional_grouped(page: Page, spec: "AggSpec", perm_sorted, seg,
                        n) -> Column:
    """min_by/max_by/arbitrary over sorted groups: pick ONE row per group
    (first at the y-extremum / first non-null), then gather x from it."""
    xcol = page.column(spec.input)
    xv = jnp.take(xcol.values, perm_sorted, mode="clip")
    xm = jnp.take(xcol.valid_mask(), perm_sorted, mode="clip")
    eligible = seg < n
    if spec.mask_channel is not None:
        fcol = page.column(spec.mask_channel)
        eligible = eligible & jnp.take(fcol.values & fcol.valid_mask(),
                                       perm_sorted, mode="clip")
    if spec.name == "arbitrary":
        eligible = eligible & xm
    else:
        ycol = page.column(spec.input2)
        yv = _nan_as_largest(jnp.take(ycol.values, perm_sorted, mode="clip"))
        ym = jnp.take(ycol.valid_mask(), perm_sorted, mode="clip")
        eligible = eligible & ym
        is_min = spec.name == "min_by"
        ident = _ident_for(yv.dtype, is_min)
        yc = jnp.where(eligible, yv, ident)
        ext = _segment_reduce(yc, seg, n, "min" if is_min else "max")
        eligible = eligible & (yc == jnp.take(ext, seg, mode="clip"))
    pos = jnp.where(eligible, jnp.arange(n, dtype=jnp.int32), n)
    first = jax.ops.segment_min(pos, seg, num_segments=n)
    has = first < n
    idx = jnp.clip(first, 0, n - 1)
    return Column(jnp.take(xv, idx), has & jnp.take(xm, idx), xcol.type,
                  xcol.dictionary)


def _positional_global(page: Page, spec: "AggSpec", live) -> Column:
    """Single-group variant of _positional_grouped (one output row)."""
    n = page.capacity
    xcol = page.column(spec.input)
    xv, xm = xcol.values, xcol.valid_mask()
    eligible = live
    if spec.mask_channel is not None:
        fcol = page.column(spec.mask_channel)
        eligible = eligible & fcol.values & fcol.valid_mask()
    if spec.name == "arbitrary":
        eligible = eligible & xm
    else:
        ycol = page.column(spec.input2)
        yv, ym = _nan_as_largest(ycol.values), ycol.valid_mask()
        eligible = eligible & ym
        is_min = spec.name == "min_by"
        ident = _ident_for(yv.dtype, is_min)
        yc = jnp.where(eligible, yv, ident)
        ext = jnp.min(yc) if is_min else jnp.max(yc)
        eligible = eligible & (yc == ext)
    pos = jnp.where(eligible, jnp.arange(n, dtype=jnp.int32), n)
    first = jnp.min(pos, keepdims=True)
    has = first < n
    idx = jnp.clip(first, 0, n - 1)
    return Column(jnp.take(xv, idx), has & jnp.take(xm, idx), xcol.type,
                  xcol.dictionary)


def _centered_finalize(kind: str, cnt, sa, sb, caa, cbb, cab):
    """Shared finalization of the centered-moment family. First argument `a`
    is the dependent variable, second `b` the independent one
    (regr_slope(y, x) argument order); var/stddev use `a` only."""
    nf = jnp.maximum(cnt.astype(jnp.float64), 1.0)
    if kind in ("var_pop", "stddev_pop"):
        value, valid = caa / nf, cnt > 0
    elif kind in ("variance", "var_samp", "stddev", "stddev_samp"):
        value, valid = caa / jnp.maximum(nf - 1.0, 1.0), cnt > 1
    elif kind == "covar_pop":
        value, valid = cab / nf, cnt > 0
    elif kind == "covar_samp":
        value, valid = cab / jnp.maximum(nf - 1.0, 1.0), cnt > 1
    elif kind == "corr":
        denom = jnp.sqrt(caa * cbb)
        value = cab / jnp.where(denom > 0, denom, 1.0)
        valid = (cnt > 1) & (denom > 0)
    elif kind == "regr_slope":
        value = cab / jnp.where(cbb > 0, cbb, 1.0)
        valid = (cnt > 0) & (cbb > 0)
    else:  # regr_intercept = mean(a) - slope * mean(b)
        slope = cab / jnp.where(cbb > 0, cbb, 1.0)
        value = sa / nf - slope * sb / nf
        valid = (cnt > 0) & (cbb > 0)
    if kind.startswith("stddev"):
        value = jnp.sqrt(jnp.maximum(value, 0.0))
    return value, valid


def _centered_grouped(page: Page, spec: "AggSpec", perm_sorted, seg,
                      n, extra_mask=None) -> Column:
    """variance/stddev/corr/covar/regr per group: segment means first, then
    segment sums of (centered) cross-products — numerically stable where the
    raw-moment form E[x²]−E[x]² cancels."""
    acol = page.column(spec.input)
    av = _to_double(jnp.take(acol.values, perm_sorted, mode="clip"),
                    spec.input_type)
    mask = jnp.take(acol.valid_mask(), perm_sorted, mode="clip") & (seg < n)
    bivar = spec.input2 is not None
    if bivar:
        bcol = page.column(spec.input2)
        bv = _to_double(jnp.take(bcol.values, perm_sorted, mode="clip"),
                        spec.input2_type)
        mask = mask & jnp.take(bcol.valid_mask(), perm_sorted, mode="clip")
    if spec.mask_channel is not None:
        fcol = page.column(spec.mask_channel)
        mask = mask & jnp.take(fcol.values & fcol.valid_mask(), perm_sorted,
                               mode="clip")
    if extra_mask is not None:     # DISTINCT first-occurrence mask
        mask = mask & extra_mask
    cnt = jax.ops.segment_sum(mask.astype(jnp.int64), seg, num_segments=n)
    nf = jnp.maximum(cnt.astype(jnp.float64), 1.0)
    sa = jax.ops.segment_sum(jnp.where(mask, av, 0.0), seg, num_segments=n)
    da = jnp.where(mask, av - jnp.take(sa / nf, seg, mode="clip"), 0.0)
    caa = jax.ops.segment_sum(da * da, seg, num_segments=n)
    sb = cbb = cab = None
    if bivar:
        sb = jax.ops.segment_sum(jnp.where(mask, bv, 0.0), seg,
                                 num_segments=n)
        db = jnp.where(mask, bv - jnp.take(sb / nf, seg, mode="clip"), 0.0)
        cbb = jax.ops.segment_sum(db * db, seg, num_segments=n)
        cab = jax.ops.segment_sum(da * db, seg, num_segments=n)
    value, valid = _centered_finalize(spec.name, cnt, sa, sb, caa, cbb, cab)
    return Column(value, valid, T.DOUBLE, None)


def _centered_global(page: Page, spec: "AggSpec", live,
                     extra_mask=None) -> Column:
    """Single-group variant of _centered_grouped (one output row)."""
    acol = page.column(spec.input)
    av = _to_double(acol.values, spec.input_type)
    mask = acol.valid_mask() & live
    bivar = spec.input2 is not None
    if bivar:
        bcol = page.column(spec.input2)
        bv = _to_double(bcol.values, spec.input2_type)
        mask = mask & bcol.valid_mask()
    if spec.mask_channel is not None:
        fcol = page.column(spec.mask_channel)
        mask = mask & fcol.values & fcol.valid_mask()
    if extra_mask is not None:     # DISTINCT first-occurrence mask
        mask = mask & extra_mask
    cnt = jnp.sum(mask.astype(jnp.int64), keepdims=True)
    nf = jnp.maximum(cnt.astype(jnp.float64), 1.0)
    sa = jnp.sum(jnp.where(mask, av, 0.0), keepdims=True)
    da = jnp.where(mask, av - sa / nf, 0.0)
    caa = jnp.sum(da * da, keepdims=True)
    sb = cbb = cab = None
    if bivar:
        sb = jnp.sum(jnp.where(mask, bv, 0.0), keepdims=True)
        db = jnp.where(mask, bv - sb / nf, 0.0)
        cbb = jnp.sum(db * db, keepdims=True)
        cab = jnp.sum(da * db, keepdims=True)
    value, valid = _centered_finalize(spec.name, cnt, sa, sb, caa, cbb, cab)
    return Column(value, valid, T.DOUBLE, None)


def _ident_for(dtype, is_min):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf if is_min else -jnp.inf, dtype=dtype)
    if dtype == jnp.bool_:
        return jnp.asarray(is_min, dtype=dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if is_min else info.min, dtype=dtype)


def _agg_out_column(fn, spec, values, valid, dictionary=None) -> Column:
    out_t = fn.output_type(spec.input_type)
    # min/max over varchar operate on dictionary codes; keep the pool so the
    # result decodes as strings
    if not T.is_string(out_t):
        dictionary = None
    return Column(values.astype(out_t.dtype), valid, out_t, dictionary)


def _global_aggregate(page, aggs, resolved, step, partial_state_channels):
    """No GROUP BY: one output row (reference: AggregationOperator.java)."""
    live = page.row_mask()
    out_cols: List[Column] = []
    dmask_cache: dict = {}

    def distinct_mask(spec):
        key = (spec.input, spec.mask_channel)
        if key not in dmask_cache:
            dmask_cache[key] = _distinct_first_mask(page, (), spec)
        return dmask_cache[key]

    for ai, (spec, fn) in enumerate(zip(aggs, resolved)):
        if spec.name == "approx_distinct":
            out_cols.append(_hll_global(page, spec, live))
            continue
        if spec.name == "approx_percentile":
            out_cols.append(_percentile_global(page, spec, live))
            continue
        if spec.name in POSITIONAL_AGGREGATES:
            out_cols.append(_positional_global(page, spec, live))
            continue
        if spec.name in CENTERED_AGGREGATES:
            extra = distinct_mask(spec) if spec.distinct else None
            out_cols.append(_centered_global(page, spec, live, extra))
            continue
        states = fn.state(spec.input_type)
        if step == Step.FINAL:
            chans = partial_state_channels[ai]
            merged = []
            for vals, reducer in _final_state_contribs(page, states, chans,
                                                       live):
                if reducer == "sum":
                    merged.append(jnp.sum(vals, keepdims=True))
                elif reducer == "min":
                    merged.append(jnp.min(vals, keepdims=True))
                else:
                    merged.append(jnp.max(vals, keepdims=True))
            values, valid = fn.final(merged, None)
            out_cols.append(_agg_out_column(
                fn, spec, values, valid, page.column(chans[0]).dictionary))
            continue
        vals, mask, dictionary = _agg_inputs(page, spec, fn, live)
        if spec.distinct:
            mask = mask & distinct_mask(spec)
        state_arrays = []
        for sc in states:
            contrib = sc.contrib(vals, mask)
            if sc.reducer == "sum":
                state_arrays.append(jnp.sum(contrib, keepdims=True))
            elif sc.reducer == "min":
                state_arrays.append(jnp.min(contrib, keepdims=True))
            else:
                state_arrays.append(jnp.max(contrib, keepdims=True))
        if step == Step.PARTIAL:
            for sc, arr in zip(states, state_arrays):
                d = dictionary if T.is_string(sc.type) else None
                out_cols.append(Column(arr.astype(sc.type.dtype), None, sc.type,
                                       d))
        else:
            values, valid = fn.final(state_arrays, None)
            out_cols.append(_agg_out_column(fn, spec, values, valid, dictionary))
    return Page(tuple(out_cols), jnp.asarray(1, dtype=jnp.int32))
