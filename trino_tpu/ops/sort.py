"""Sort / TopN / Limit operators.

Reference parity: operator/OrderByOperator.java (389) + PagesIndex.java with
codegen'd PagesIndexComparator (sql/gen/OrderingCompiler.java), TopNOperator
.java, LimitOperator. On TPU: a stable argsort over the key columns
(ops/radix.py — passes of one small `lax.sort`, cheap to compile) with
null-ordering flags as leading sub-keys replaces comparator codegen; the
page is gathered through the resulting order.

Ordering semantics (Trino): ASC defaults to NULLS LAST, DESC to NULLS FIRST;
ORDER BY is stable w.r.t. input order via a trailing row-index key.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

import jax.numpy as jnp

from trino_tpu import types as T
from trino_tpu.ops.radix import stable_argsort
from trino_tpu.page import Page, op_scope


@dataclasses.dataclass(frozen=True)
class SortKey:
    channel: int
    ascending: bool = True
    nulls_first: Optional[bool] = None  # None = Trino default for direction

    def resolved_nulls_first(self) -> bool:
        if self.nulls_first is not None:
            return self.nulls_first
        return not self.ascending


def _descending_form(values: jnp.ndarray) -> jnp.ndarray:
    """Map values so ascending sort yields descending order."""
    if values.dtype == jnp.bool_:
        return ~values
    if jnp.issubdtype(values.dtype, jnp.floating):
        # flip sign; NaN handled by leading nan-flag key (Trino: NaN largest)
        return -values
    if jnp.issubdtype(values.dtype, jnp.unsignedinteger):
        return ~values
    return -values  # int overflow only at INT_MIN; acceptable round 1


def _sort_operands(page: Page, keys: Sequence[SortKey]):
    dead = ~page.row_mask()
    operands = [dead]
    for k in keys:
        col = page.column(k.channel)
        values = col.values
        is_float = jnp.issubdtype(values.dtype, jnp.floating)
        if col.valid is not None:
            null_flag = ~col.valid
            flag = ~null_flag if k.resolved_nulls_first() else null_flag
            operands.append(flag)
            values = jnp.where(col.valid, values, jnp.zeros((), values.dtype))
        if is_float:
            # Trino orders NaN as largest; XLA's default float order already
            # totals NaN last ascending, but make it explicit & desc-correct
            nan = jnp.isnan(values)
            nan_key = nan if k.ascending else ~nan
            operands.append(nan_key)
            values = jnp.where(nan, jnp.zeros((), values.dtype), values)
        operands.append(values if k.ascending else _descending_form(values))
    return operands


def order_by(keys: Sequence[SortKey]) -> Callable[[Page], Page]:
    """Full sort of the page by keys (stable)."""
    keys = tuple(keys)

    def op(page: Page) -> Page:
        with op_scope("sort__order_keys"):
            order = stable_argsort(_sort_operands(page, keys))
        with op_scope("sort__payload_gather"):
            return page.gather(order, page.num_rows)

    return op


def top_n_masked(keys: Sequence[SortKey]) -> Callable[[Page, Any], Page]:
    """ORDER BY ... LIMIT ? with the COUNT as a runtime operand: the
    sort runs at full page capacity and the count only masks `num_rows`,
    so nothing in the traced program depends on it — one jitted
    executable (keyed literal-free, like a hoisted parameter) serves
    LIMIT 5 and LIMIT 500 of the same shape. This is what lets a warmup
    manifest cover a whole `LIMIT k` family with one compile."""
    sort_op = order_by(keys)

    def op(page: Page, count) -> Page:
        out = sort_op(page)
        return Page(out.columns,
                    jnp.minimum(out.num_rows,
                                jnp.asarray(count, dtype=jnp.int32)))

    return op


def top_n(count: int, keys: Sequence[SortKey]) -> Callable[[Page], Page]:
    """ORDER BY ... LIMIT n with the count baked in (TopNOperator
    analog): the masked kernel with a fixed count — mesh programs and
    other static callers keep this shape."""
    masked = top_n_masked(keys)

    def op(page: Page) -> Page:
        return masked(page, count)

    return op


def limit(count: int) -> Callable[[Page], Page]:
    def op(page: Page) -> Page:
        return Page(page.columns, jnp.minimum(page.num_rows, count))

    return op
