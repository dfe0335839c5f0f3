"""Expression compiler: RowExpression -> traced jnp function over a Page.

Reference parity: sql/gen/ExpressionCompiler.java:56 + PageFunctionCompiler
.java:101. Where the reference emits JVM bytecode per expression tree, we
recursively build a jnp computation; under jit, XLA fuses the whole filter/
project with adjacent operator kernels (the PageProcessor role).

Null semantics are SQL three-valued logic, carried as (values, valid) pairs:
- default functions: result null iff any input null (RETURNS NULL ON NULL)
- AND/OR: Kleene logic (false AND null = false, true OR null = true)
- comparisons with null: null; WHERE treats null as false (compile_filter)

Dictionary folding happens at trace time (dictionaries are static aux data):
  varchar_col = 'FOO'   -> codes == dict.code_of('FOO')
  varchar_col < 'FOO'   -> codes < dict.lower_bound('FOO')
  varchar_col LIKE 'F%' -> gather of a host-computed boolean table by code
so string predicates cost one int32 compare/gather per row on device.
On the chain path the LIKE table is no constant of the trace but an
operand (`$like_table`, expr/hoist.py, PR 42): the trace depends on the
dictionary alone, and every pattern runs the one executable.
"""

from __future__ import annotations

import re
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.expr import functions as F
from trino_tpu.expr.ir import (
    Call, InputRef, Literal, Param, RowExpression, SpecialForm, SpecialKind)
from trino_tpu.page import Column, Dictionary, Page

_COMPARISONS = {"eq", "ne", "lt", "le", "gt", "ge"}

# ---------------------------------------------------------------------------
# Literal-hoisting whitelist (expr/hoist.py consults this table).
#
# Call sites below REQUIRE `isinstance(arg, Literal)` at trace time because
# the literal's VALUE determines trace shape or feeds host-side dictionary
# work: LIKE/regex patterns compile per-pool boolean tables, string-function
# literals parameterize host dictionary transforms, date/format units pick
# the kernel, list lengths size planes. Hoisting one of these into a traced
# Param would either fail loudly (the isinstance checks) or silently bake a
# stale table into a shared kernel — so the hoister leaves the annotated
# argument positions (or, for "all", the entire call) untouched. Every entry
# names the evaluator that owns the constraint, so correctness is auditable
# next to the code that enforces it.
#
#   name -> frozenset of arg positions that must stay Literal, or "all"
#   (skip the whole call — no hoisting anywhere beneath it).
STATIC_LITERAL_ARGS = {
    # _like: pattern + escape build a host like-table over the dictionary.
    # (The hoister takes a `like` of the chain path before it looks here:
    # `$like_table` below gets that table as an operand.)
    "like": frozenset({1, 2}),
    # _date_unit_call: the unit string selects the arithmetic at trace time
    "date_trunc": frozenset({0}),
    "date_diff": frozenset({0}),
    "date_add": frozenset({0}),
    # _format_datetime: the pattern formats the whole day domain host-side
    "format_datetime": frozenset({1}),
    "date_format": frozenset({1}),
}
# _string_transform/_string_scalar/_concat_ws (_column_and_literals): every
# literal argument parameterizes a memoized host-side dictionary table, and
# the column argument's subtree is evaluated inside that machinery — keep
# the entire call static.
for _name in ("lower", "upper", "trim", "ltrim", "rtrim", "substr",
              "substring", "concat", "replace", "reverse", "lpad", "rpad",
              "split_part", "regexp_replace", "regexp_extract", "concat_ws",
              "length", "codepoint", "strpos", "regexp_like", "starts_with"):
    STATIC_LITERAL_ARGS[_name] = "all"


def _vand(a: Optional[jnp.ndarray], b: Optional[jnp.ndarray]):
    if a is None:
        return b
    if b is None:
        return a
    return a & b


def _lit_column(lit: Literal) -> Column:
    typ = lit.type
    if lit.value is None:
        return Column(jnp.zeros((), dtype=typ.dtype),
                      jnp.zeros((), dtype=jnp.bool_), typ, None)
    if T.is_string(typ):
        # projected string literal: singleton dictionary, every row code 0
        # (comparisons never reach here — they fold against the column's
        # dictionary first)
        import numpy as np
        d = Dictionary(np.asarray([lit.value], dtype=object))
        return Column(jnp.zeros((), dtype=jnp.int32), None, typ, d)
    value = lit.value
    if isinstance(typ, T.DecimalType):
        # literals carried as ints already scaled by the frontend
        value = int(value)
    return Column(jnp.asarray(value, dtype=typ.dtype), None, typ, None)


def _eval(expr: RowExpression, page: Page, params=()) -> Column:
    if isinstance(expr, InputRef):
        return page.columns[expr.index]
    if isinstance(expr, Literal):
        return _lit_column(expr)
    if isinstance(expr, Param):
        # hoisted literal: a traced 0-d scalar operand (expr/hoist.py
        # guarantees numeric/temporal, non-null, so valid=None and no
        # dictionary — the same Column shape _lit_column builds)
        return Column(jnp.asarray(params[expr.index]), None, expr.type, None)
    if isinstance(expr, Call):
        return _eval_call(expr, page, params)
    if isinstance(expr, SpecialForm):
        return _eval_special(expr, page, params)
    raise TypeError(f"unknown expression node: {expr!r}")


def _string_side(args) -> bool:
    return any(T.is_string(a.type) for a in args)


def _eval_call(expr: Call, page: Page, params=()) -> Column:
    name = expr.name
    # --- dictionary-folded string paths -----------------------------------
    if name in _COMPARISONS and _string_side(expr.args):
        return _string_comparison(name, expr.args, page, expr.type, params)
    if name == "like":
        return _like(expr, page, params)
    if name in ("lower", "upper", "trim", "ltrim", "rtrim", "substr",
                "substring", "concat", "replace", "reverse", "lpad", "rpad",
                "split_part", "regexp_replace", "regexp_extract",
                "concat_ws"):
        return _string_transform(expr, page, params)
    if name in ("length", "codepoint", "strpos", "regexp_like",
                "starts_with"):
        return _string_scalar(expr, page, params)
    if name in ("date_trunc", "date_diff", "date_add"):
        return _date_unit_call(expr, page, params)
    if name == "try_cast":
        return _try_cast(expr, page, params)
    if name in ("array_ctor", "cardinality", "element_at",
                "map_element_at", "contains"):
        return _array_call(expr, page, params)
    if name in ("format_datetime", "date_format"):
        return _format_datetime(expr, page, params)
    if name == "$in_padded":
        return _in_padded(expr, page, params)
    if name == "$like_table":
        return _like_table(expr, page, params)
    # --- generic null-propagating scalar ----------------------------------
    impl = F.lookup(name)
    args = [_eval(a, page, params) for a in expr.args]
    values = impl(expr.type, [a.type for a in expr.args],
                  *[a.values for a in args])
    valid = None
    for a in args:
        valid = _vand(valid, a.valid)
    return Column(values, valid, expr.type, None)


def _in_padded(expr: Call, page: Page, params=()) -> Column:
    """Padded fixed-width IN-list membership (expr/hoist._pad_in_chain):
    args are (needle, Param -> padded member vector, static width
    Literal). The member vector arrives as a traced 1-d operand of the
    bucket width, so every list length within a bucket runs one
    executable; padding repeats a real member, so no mask is needed.
    Null semantics match the OR-of-eq desugaring it replaces: members
    are non-null by construction, so the result is null iff the needle
    is null (Kleene OR of needle-null equality tests)."""
    col = _eval(expr.args[0], page, params)
    vec = jnp.asarray(params[expr.args[1].index])
    vals = jnp.any(col.values[..., None] == vec, axis=-1)
    return Column(vals, col.valid, expr.type, None)


def _literal_str(expr: RowExpression) -> Optional[str]:
    if isinstance(expr, Literal) and T.is_string(expr.type):
        return expr.value
    return None


def _string_comparison(name: str, args, page: Page, out_type,
                       params=()) -> Column:
    a_lit, b_lit = _literal_str(args[0]), _literal_str(args[1])
    if a_lit is not None and b_lit is not None:
        # constant fold
        result = {
            "eq": a_lit == b_lit, "ne": a_lit != b_lit, "lt": a_lit < b_lit,
            "le": a_lit <= b_lit, "gt": a_lit > b_lit, "ge": a_lit >= b_lit,
        }[name]
        return Column(jnp.asarray(result), None, out_type, None)
    if b_lit is None and a_lit is not None:
        # normalize literal to the right: lit <op> col == col <flip op> lit
        flip = {"eq": "eq", "ne": "ne", "lt": "gt", "le": "ge",
                "gt": "lt", "ge": "le"}[name]
        return _string_comparison(flip, (args[1], args[0]), page, out_type,
                                  params)
    col = _eval(args[0], page, params)
    if b_lit is not None:
        d = col.dictionary
        if d is None:
            raise NotImplementedError("string comparison without dictionary")
        codes = col.values
        if name == "eq":
            code = d.code_of(b_lit)
            vals = (codes == code) if code >= 0 else jnp.zeros_like(codes, dtype=jnp.bool_)
        elif name == "ne":
            code = d.code_of(b_lit)
            vals = (codes != code) if code >= 0 else jnp.ones_like(codes, dtype=jnp.bool_)
        elif name == "lt":
            vals = codes < d.lower_bound(b_lit)
        elif name == "le":
            vals = codes < d.upper_bound(b_lit)
        elif name == "gt":
            vals = codes >= d.upper_bound(b_lit)
        else:  # ge
            vals = codes >= d.lower_bound(b_lit)
        return Column(vals, col.valid, out_type, None)
    # column vs column: only valid when both sides share one dictionary
    # (content-fingerprint equality — byte-identical pools from different
    # tables have the same code mapping, so code comparison is exact)
    other = _eval(args[1], page, params)
    if col.dictionary != other.dictionary:
        raise NotImplementedError(
            "string column comparison across distinct dictionaries")
    vals = F.lookup(name)(out_type, [T.BIGINT, T.BIGINT],
                          col.values, other.values)
    return Column(vals, _vand(col.valid, other.valid), out_type, None)


def _like(expr: Call, page: Page, params=()) -> Column:
    col = _eval(expr.args[0], page, params)
    pattern = _literal_str(expr.args[1])
    if pattern is None or col.dictionary is None:
        raise NotImplementedError("LIKE requires literal pattern + dictionary")
    escape = None
    if len(expr.args) > 2:
        escape = _literal_str(expr.args[2])
    table = F.like_table(col.dictionary, pattern, escape)
    if not len(table):      # an empty dictionary: a row to clip to
        table = np.zeros(1, dtype=np.bool_)
    vals = jnp.take(table, col.values, mode="clip")
    return Column(vals, col.valid, expr.type, None)


def _like_table(expr: Call, page: Page, params=()) -> Column:
    """`$like_table(col, Param)`: LIKE with the boolean table over the
    column's dictionary as an operand — `hoist.LikeOperand.table`, built
    by the dispatcher on the host from the dictionary of the page in hand.
    A Param that still holds the `LikeOperand` itself is the abstract
    trace by which the dispatcher learns which dictionary that is."""
    from trino_tpu.expr.hoist import LikeOperand, found_dictionary
    col = _eval(expr.args[0], page, params)
    if col.dictionary is None:
        raise NotImplementedError("LIKE requires a dictionary")
    table = params[expr.args[1].index]
    if isinstance(table, LikeOperand):
        found_dictionary(table, col.dictionary)
        table = table.placeholder(col.dictionary)
    vals = jnp.take(jnp.asarray(table), col.values, mode="clip")
    return Column(vals, col.valid, expr.type, None)


def _column_and_literals(expr: Call, page: Page, params=()):
    """First non-literal arg is THE column; every other arg must be a
    literal (STATIC_LITERAL_ARGS marks these calls "all", so the hoister
    never rewrites them to Params). Returns (column, call(s) -> py fn
    applied with the column's string substituted at its ORIGINAL argument
    position, memo key)."""
    col_i = None
    for i, a in enumerate(expr.args):
        if not isinstance(a, Literal):
            if col_i is not None:
                raise NotImplementedError(
                    f"{expr.name} over two non-literal string args")
            col_i = i
    if col_i is None:
        col_i = 0   # all-literal: fold through the first arg's singleton
    col = _eval(expr.args[col_i], page, params)
    lit_by_pos = {i: a.value for i, a in enumerate(expr.args) if i != col_i}

    def call(fn, s):
        args = [s if i == col_i else lit_by_pos[i]
                for i in range(len(expr.args))]
        return fn(*args)
    key = (col_i,) + tuple(sorted(lit_by_pos.items()))
    return col, call, key


def _string_transform(expr: Call, page: Page, params=()) -> Column:
    """str->str functions as dictionary remap (host transform, device
    gather). NULL-producing transforms (split_part past the last field,
    regexp_extract without a match) carry a per-pool-value ok-table."""
    name = expr.name
    if name == "concat_ws":
        return _concat_ws(expr, page, params)
    col, call, akey = _column_and_literals(expr, page, params)
    if col.dictionary is None:
        raise NotImplementedError(f"{name} requires dictionary-encoded input")
    py = _PY_STRING_FNS[name]
    key = (name, akey)
    if name in _NULLABLE_STRING_FNS:
        nd, remap, ok = F.transform_dictionary_nullable(
            col.dictionary, key, lambda s: call(py, s))
        codes = jnp.take(remap, col.values, mode="clip")
        okv = jnp.take(jnp.asarray(ok), col.values, mode="clip")
        valid = okv if col.valid is None else (okv & col.valid)
        return Column(codes, valid, expr.type, nd)
    nd, remap = F.transform_dictionary(col.dictionary, key,
                                       lambda s: call(py, s))
    codes = jnp.take(remap, col.values, mode="clip")
    return Column(codes, col.valid, expr.type, nd)


def _concat_ws(expr: Call, page: Page, params=()) -> Column:
    """concat_ws(sep, v1, v2, ...): Trino skips NULL value arguments and
    returns NULL only for a NULL separator (StringFunctions.java concatWs)
    — unlike the generic AND-of-valid-masks path."""
    sep_e = expr.args[0]
    if not isinstance(sep_e, Literal):
        raise NotImplementedError("concat_ws separator must be a literal")
    if sep_e.value is None:
        return Column(jnp.zeros((), dtype=jnp.int32),
                      jnp.zeros((), dtype=jnp.bool_), expr.type,
                      Dictionary(np.asarray([""], dtype=object)))
    sep = str(sep_e.value)
    col_i = None
    for i, a in enumerate(expr.args[1:], start=1):
        if not isinstance(a, Literal):
            if col_i is not None:
                raise NotImplementedError(
                    "concat_ws over two non-literal string args")
            col_i = i
    lits = {i: a.value for i, a in enumerate(expr.args) if i != col_i
            and i > 0}
    if col_i is None:
        joined = sep.join(str(v) for v in lits.values() if v is not None)
        d = Dictionary(np.asarray([joined], dtype=object))
        return Column(jnp.zeros((), dtype=jnp.int32), None, expr.type, d)
    col = _eval(expr.args[col_i], page, params)
    if col.dictionary is None:
        raise NotImplementedError("concat_ws requires dictionary input")

    def join_with(s):
        # s = None models a NULL column value: dropped from the join
        parts = [lits[i] if i != col_i else s
                 for i in range(1, len(expr.args))]
        return sep.join(str(p) for p in parts if p is not None)

    cache = F._dict_cache(col.dictionary)
    ck = ("concat_ws", sep, tuple(sorted(lits.items())), col_i, "xform")
    if ck not in cache:
        table = [join_with(s) for s in col.dictionary.values] \
            + [join_with(None)]
        new_vals, codes = np.unique(np.asarray(table, dtype=object),
                                    return_inverse=True)
        cache[ck] = (Dictionary(new_vals), codes[:-1].astype(np.int32),
                     int(codes[-1]))
    nd, remap, null_code = cache[ck]
    out = jnp.take(jnp.asarray(remap), col.values, mode="clip")
    if col.valid is not None:
        out = jnp.where(col.valid, out, null_code)
    return Column(out, None, expr.type, nd)


_STRING_SCALAR_FNS = {
    "length": (lambda s: len(s), jnp.int64),
    "codepoint": (lambda s: ord(s[0]) if s else 0, jnp.int64),
    "strpos": (lambda s, sub: s.find(sub) + 1, jnp.int64),
    "regexp_like": (lambda s, pat: re.search(pat, s) is not None, jnp.bool_),
    "starts_with": (lambda s, pre: s.startswith(pre), jnp.bool_),
}


def _string_scalar(expr: Call, page: Page, params=()) -> Column:
    """str -> number/bool functions as a memoized per-pool host table +
    device gather (the joni/re2j per-row regex replacement)."""
    name = expr.name
    col, call, akey = _column_and_literals(expr, page, params)
    if col.dictionary is None:
        raise NotImplementedError(f"{name} requires dictionary-encoded input")
    fn, dtype = _STRING_SCALAR_FNS[name]
    table = F.dictionary_table(col.dictionary, (name, akey),
                               lambda s: call(fn, s))
    vals = jnp.take(jnp.asarray(table), col.values,
                    mode="clip").astype(dtype)
    return Column(vals, col.valid, expr.type, None)


_DATE_UNITS_TS = {"second": 1_000_000, "minute": 60_000_000,
                  "hour": 3_600_000_000, "day": 86_400_000_000,
                  "millisecond": 1_000}


def _date_unit_call(expr: Call, page: Page, params=()) -> Column:
    """date_trunc / date_diff / date_add with a literal unit
    (DateTimeFunctions.java parity for DATE; micros arithmetic for the
    sub-day TIMESTAMP units)."""
    unit_arg = expr.args[0]
    if not isinstance(unit_arg, Literal):
        raise NotImplementedError(f"{expr.name} unit must be a literal")
    unit = str(unit_arg.value).lower()
    rest = [_eval(a, page, params) for a in expr.args[1:]]
    valid = None
    for a in rest:
        valid = _vand(valid, a.valid)
    name = expr.name
    if name == "date_trunc":
        (col,) = rest
        if isinstance(expr.type, T.DateType):
            vals = F.date_trunc_days(unit, col.values)
        elif unit in _DATE_UNITS_TS:
            step = jnp.int64(_DATE_UNITS_TS[unit])
            v = col.values.astype(jnp.int64)
            vals = (jax.lax.div(jnp.where(v >= 0, v, v - step + 1), step)
                    * step)
        else:
            raise NotImplementedError(
                f"date_trunc({unit!r}) on {expr.type.display()}")
        return Column(vals, valid, expr.type, None)
    if name == "date_diff":
        a, b = rest
        at, bt = expr.args[1].type, expr.args[2].type
        if isinstance(at, T.DateType) and isinstance(bt, T.DateType):
            vals = F.date_diff_days(unit, a.values, b.values)
        elif isinstance(at, T.TimestampType) and \
                isinstance(bt, T.TimestampType) and unit in _DATE_UNITS_TS:
            step = jnp.int64(_DATE_UNITS_TS[unit])
            vals = jax.lax.div(b.values.astype(jnp.int64)
                               - a.values.astype(jnp.int64), step)
        else:
            # mixed DATE/TIMESTAMP operands must be coerced upstream —
            # day-number vs microsecond arithmetic would be garbage
            raise NotImplementedError(
                f"date_diff({unit!r}) over {at.display()}, {bt.display()}")
        return Column(vals, valid, expr.type, None)
    # date_add(unit, n, temporal)
    n, d = rest
    dt = expr.args[2].type
    if isinstance(expr.type, T.DateType) and isinstance(dt, T.DateType):
        vals = F.date_add_days(unit, n.values.astype(jnp.int64), d.values)
    elif isinstance(dt, T.TimestampType) and unit in _DATE_UNITS_TS:
        vals = d.values.astype(jnp.int64) + n.values.astype(jnp.int64) \
            * jnp.int64(_DATE_UNITS_TS[unit])
    else:
        raise NotImplementedError(
            f"date_add({unit!r}) on {dt.display()}")
    return Column(vals, valid, expr.type, None)


def _try_cast(expr: Call, page: Page, params=()) -> Column:
    """TRY_CAST: NULL instead of failure. Non-string sources delegate to
    the saturating cast kernel (which cannot raise per-row); varchar
    sources parse the dictionary pool host-side into a value table + an
    ok-mask table."""
    target = expr.type
    src_t = expr.args[0].type
    col = _eval(expr.args[0], page, params)
    if not T.is_string(src_t):
        values = F.lookup("cast")(target, [src_t], col.values)
        ok = _numeric_cast_ok(col.values, src_t, target)
        valid = col.valid if ok is None else _vand(col.valid, ok)
        return Column(values, valid, target,
                      col.dictionary if T.is_string(target) else None)
    if col.dictionary is None:
        raise NotImplementedError("try_cast requires dictionary input")
    if T.is_string(target):
        return Column(col.values, col.valid, target, col.dictionary)
    parse = _py_parser_for(target)
    table = F.dictionary_table(
        col.dictionary, ("try_cast", target.display()),
        lambda s: parse(s))
    vals_np = np.asarray(
        [0 if v is None else v for v in table],
        dtype=T.to_numpy_dtype(target))
    ok_np = np.asarray([v is not None for v in table])
    vals = jnp.take(jnp.asarray(vals_np), col.values, mode="clip")
    okv = jnp.take(jnp.asarray(ok_np), col.values, mode="clip")
    valid = okv if col.valid is None else (okv & col.valid)
    return Column(vals, valid, target, None)


_INT_TYPES = (T.BigintType, T.IntegerType, T.SmallintType, T.TinyintType)


_I64 = (-(1 << 63), (1 << 63) - 1)


def _int_range_ok(v: jnp.ndarray, lo: int, hi: int
                  ) -> Optional[jnp.ndarray]:
    """v (int64) within [lo, hi], with bounds that may exceed int64 —
    a bound outside int64 can never be violated, so that side is skipped
    (jnp would raise OverflowError promoting an out-of-range Python int)."""
    ok = None
    if lo > _I64[0]:
        ok = v >= lo
    if hi < _I64[1]:
        c = v <= hi
        ok = c if ok is None else (ok & c)
    return ok


def _numeric_cast_ok(values: jnp.ndarray, src_t, target
                     ) -> Optional[jnp.ndarray]:
    """Out-of-range mask for TRY_CAST on numeric sources: Trino returns
    NULL where the plain CAST would fail, while the shared cast kernel
    saturates (it cannot raise per-row). None = always representable.
    Integer comparisons stay in exact int64 arithmetic (float64 rounding
    misclassifies values near 2^53..2^63 boundaries)."""
    if isinstance(target, _INT_TYPES):
        info = jnp.iinfo(target.dtype)
        if jnp.issubdtype(values.dtype, jnp.floating):
            v = values
            if int(info.max) == _I64[1]:
                # float64(int64.max) rounds UP to exactly 2^63: exclusive
                return jnp.isfinite(v) & (v >= float(info.min)) \
                    & (v < 9223372036854775808.0)
            return jnp.isfinite(v) & (v >= float(info.min)) \
                & (v <= float(info.max))
        v = values.astype(jnp.int64)
        if isinstance(src_t, T.DecimalType):
            # scaled-int source: target range in source-scaled units
            scale = 10 ** src_t.scale
            return _int_range_ok(v, int(info.min) * scale,
                                 int(info.max) * scale)
        return _int_range_ok(v, int(info.min), int(info.max))
    if isinstance(target, T.DecimalType):
        # cast multiplies by 10^scale; NULL when |v| >= 10^(p-s)
        if jnp.issubdtype(values.dtype, jnp.floating):
            bound = float(10 ** (target.precision - target.scale))
            v = values
            return jnp.isfinite(v) & (v > -bound) & (v < bound)
        # integer/decimal source: exact integer bound in SOURCE units
        src_scale = src_t.scale if isinstance(src_t, T.DecimalType) else 0
        bound = 10 ** (target.precision - target.scale + src_scale)
        v = values.astype(jnp.int64)
        return _int_range_ok(v, -(bound - 1), bound - 1)
    return None   # float/bool/date targets: saturation matches Trino


_DATE_FMT_CACHE: dict = {}
_FMT_BASE_Y, _FMT_END_Y = 1900, 2100


def _joda_to_strftime(pattern: str) -> str:
    """Joda (format_datetime) -> strftime, date-resolution subset."""
    out = []
    i = 0
    while i < len(pattern):
        ch = pattern[i]
        run = 1
        while i + run < len(pattern) and pattern[i + run] == ch:
            run += 1
        tok = ch * run
        mapping = {"yyyy": "%Y", "yy": "%y", "y": "%Y", "MMMM": "%B",
                   "MMM": "%b", "MM": "%m", "M": "%-m", "dd": "%d",
                   "d": "%-d", "EEEE": "%A", "EEE": "%a", "e": "%u",
                   "DDD": "%j", "D": "%-j"}
        if ch in "HhmsSaKkZzwQx":
            # time-of-day tokens are unrepresentable on a day-resolution
            # table; 'w' (Joda ISO week-of-weekyear) has no strftime
            # equivalent ('%W' is zero-based Monday weeks) — fail loud
            raise NotImplementedError(
                f"format_datetime token {tok!r} unsupported on DATE")
        out.append(mapping.get(tok, tok))
        i += run
    return "".join(out)


def _mysql_to_strftime(pattern: str) -> str:
    """MySQL (date_format) -> strftime, date-resolution subset."""
    out = []
    i = 0
    while i < len(pattern):
        if pattern[i] == "%" and i + 1 < len(pattern):
            c = pattern[i + 1]
            mapping = {"Y": "%Y", "y": "%y", "m": "%m", "c": "%-m",
                       "d": "%d", "e": "%-d", "j": "%j", "W": "%A",
                       "a": "%a", "M": "%B", "b": "%b", "u": "%W",
                       "%": "%%"}
            if c in "HhiSsTrpf":
                raise NotImplementedError(
                    f"date_format time-of-day token %{c} on DATE")
            out.append(mapping.get(c, "%" + c))
            i += 2
        else:
            out.append(pattern[i])
            i += 1
    return "".join(out)


def _format_datetime(expr: Call, page: Page, params=()) -> Column:
    """format_datetime/date_format with a literal pattern over DATE (and
    day-resolution TIMESTAMP) columns: the whole 1900-2100 day domain
    formats ONCE into a memoized dictionary + code table, so the device
    does one gather per row (DateTimeFunctions.java's per-row formatter
    replaced by a bounded-domain lookup — the dictionary-encoding move
    this engine makes for every string computation)."""
    pat = expr.args[1]
    if not isinstance(pat, Literal):
        raise NotImplementedError(f"{expr.name} pattern must be a literal")
    col = _eval(expr.args[0], page, params)
    src_t = expr.args[0].type
    values = col.values
    if isinstance(src_t, T.TimestampType):
        values = (values.astype(jnp.int64)
                  // jnp.int64(86_400_000_000)).astype(jnp.int32)
    elif not isinstance(src_t, T.DateType):
        raise NotImplementedError(
            f"{expr.name} over {src_t.display()}")
    key = (expr.name, pat.value)
    got = _DATE_FMT_CACHE.get(key)
    if got is None:
        import datetime as _dt
        fmt = _joda_to_strftime(pat.value) if expr.name == "format_datetime" \
            else _mysql_to_strftime(pat.value)
        base = _dt.date(_FMT_BASE_Y, 1, 1)
        days0 = (base - _dt.date(1970, 1, 1)).days
        ndays = (_dt.date(_FMT_END_Y, 1, 1) - base).days
        strings = np.asarray(
            [(base + _dt.timedelta(days=i)).strftime(fmt)
             for i in range(ndays)]
            # explicit out-of-domain marker (silently clipping to the
            # boundary would format extreme dates as 1900/2099 strings)
            + [f"<date out of {_FMT_BASE_Y}-{_FMT_END_Y}>"], dtype=object)
        uniq, remap = np.unique(strings, return_inverse=True)
        got = _DATE_FMT_CACHE[key] = (
            Dictionary(uniq), jnp.asarray(remap.astype(np.int32)),
            days0, ndays)
    d, remap, days0, ndays = got
    off = values.astype(jnp.int64) - days0
    oob = (off < 0) | (off >= ndays)
    off = jnp.where(oob, ndays, off)    # marker slot
    codes = jnp.take(remap, off, mode="clip")
    return Column(codes.astype(jnp.int32), col.valid, expr.type, d)


def _array_call(expr: Call, page: Page, params=()) -> Column:
    """ARRAY scalar surface over the list layout (values [cap, L] +
    lengths; spi/block/ArrayBlock re-cut for static shapes). Element
    NULLs are not represented (documented deviation)."""
    name = expr.name
    cap = page.capacity
    if name == "array_ctor":
        args = [_broadcast(_eval(a, page, params), cap) for a in expr.args]
        dicts = [a.dictionary for a in args if a.dictionary is not None]
        dictionary = None
        if dicts:
            uniq = {id(d): d for d in dicts}
            if len(uniq) == 1:
                dictionary = dicts[0]
            else:
                from trino_tpu.page import union_dictionaries
                dictionary, tables = union_dictionaries(
                    list(uniq.values()))
                remap = dict(zip(uniq, tables))
                args = [
                    Column(jnp.take(remap[id(a.dictionary)],
                                    jnp.clip(a.values, 0), mode="clip"),
                           a.valid, a.type, dictionary)
                    if a.dictionary is not None else a
                    for a in args]
        elem_dt = expr.type.element.dtype
        values = jnp.stack(
            [a.values.astype(elem_dt) for a in args], axis=1)
        lengths = jnp.full(cap, len(args), dtype=jnp.int32)
        valid = None
        for a in args:
            valid = _vand(valid, a.valid)
        return Column(values, valid, expr.type, dictionary,
                      lengths=lengths)
    arr = _eval(expr.args[0], page, params)
    if arr.lengths is None:
        raise NotImplementedError(f"{name} over a non-list column")
    L = arr.values.shape[1]
    iota = jnp.arange(L, dtype=jnp.int32)[None, :]
    in_len = iota < arr.lengths[:, None]
    if name == "cardinality":
        return Column(arr.lengths.astype(jnp.int64), arr.valid,
                      expr.type, None)
    if name == "element_at":
        i = _broadcast(_eval(expr.args[1], page, params), cap)
        iv = i.values.astype(jnp.int32)
        idx = jnp.where(iv < 0, arr.lengths + iv, iv - 1)
        inb = (iv != 0) & (idx >= 0) & (idx < arr.lengths)
        vals = jnp.take_along_axis(
            arr.values, jnp.clip(idx, 0, max(L - 1, 0))[:, None],
            axis=1)[:, 0]
        valid = _vand(_vand(arr.valid, i.valid), inb)
        return Column(vals, valid, expr.type, arr.dictionary)
    if name in ("contains", "map_element_at"):
        x = _broadcast(_eval(expr.args[1], page, params), cap)
        xv = x.values
        if arr.dictionary is not None:
            if x.dictionary is arr.dictionary:
                pass
            elif isinstance(expr.args[1], Literal):
                code = arr.dictionary.code_of(expr.args[1].value)
                xv = jnp.full(cap, code, dtype=arr.values.dtype)
            else:
                raise NotImplementedError(
                    "array membership across distinct dictionaries")
        match = (arr.values == xv[:, None]) & in_len
        if name == "contains":
            return Column(jnp.any(match, axis=1),
                          _vand(arr.valid, x.valid), expr.type, None)
        found = jnp.any(match, axis=1)
        idx = jnp.argmax(match, axis=1)
        vals = jnp.take_along_axis(arr.aux, idx[:, None], axis=1)[:, 0]
        valid = _vand(_vand(arr.valid, x.valid), found)
        return Column(vals, valid, expr.type, arr.aux_dictionary)
    raise TypeError(name)


def _py_parser_for(target):
    """Python parser matching Trino varchar->X cast semantics; None = NULL."""
    import decimal as _dec
    if isinstance(target, (T.BigintType, T.IntegerType, T.SmallintType,
                           T.TinyintType)):
        def parse_int(s):
            try:
                return int(s.strip())
            except ValueError:
                return None
        return parse_int
    if isinstance(target, (T.DoubleType, T.RealType)):
        def parse_float(s):
            try:
                return float(s.strip())
            except ValueError:
                return None
        return parse_float
    if isinstance(target, T.DecimalType):
        def parse_dec(s):
            try:
                q = _dec.Decimal(s.strip()).scaleb(target.scale)
                return int(q.to_integral_value(rounding=_dec.ROUND_HALF_UP))
            except (_dec.InvalidOperation, ValueError):
                return None
        return parse_dec
    if isinstance(target, T.DateType):
        def parse_date(s):
            try:
                y, m, d = s.strip().split("-")
                return F.days_from_civil(int(y), int(m), int(d))
            except (ValueError, AttributeError):
                return None
        return parse_date
    if isinstance(target, T.BooleanType):
        def parse_bool(s):
            v = s.strip().lower()
            if v in ("true", "t", "1"):
                return True
            if v in ("false", "f", "0"):
                return False
            return None
        return parse_bool
    raise NotImplementedError(f"try_cast to {target.display()}")


def _py_substr(s: str, start: int, length: Optional[int] = None) -> str:
    # SQL substr is 1-based; negative start counts from the end (Trino)
    if start > 0:
        i = start - 1
    elif start < 0:
        i = len(s) + start
        if i < 0:
            return ""
    else:
        return ""
    piece = s[i:]
    if length is not None:
        piece = piece[:max(length, 0)]
    return piece


def _py_pad(s: str, size: int, pad: str, left: bool) -> str:
    # StringFunctions.java lpad/rpad: truncate when longer; cycle the pad
    size = int(size)
    if len(s) >= size:
        return s[:size]
    fill = (pad * ((size - len(s)) // max(len(pad), 1) + 1))[:size - len(s)]
    return fill + s if left else s + fill


def _py_split_part(s: str, delim: str, index: int):
    parts = s.split(delim) if delim else [s]
    return parts[index - 1] if 1 <= index <= len(parts) else None


def _py_regexp_replace(s: str, pattern: str, repl: str = "") -> str:
    # Trino uses $g group references; re wants \g
    return re.sub(pattern, re.sub(r"\$(\d+)", r"\\\1", repl), s)


def _py_regexp_extract(s: str, pattern: str, group: int = 0):
    m = re.search(pattern, s)
    if m is None:
        return None
    return m.group(group)


_PY_STRING_FNS = {
    "lower": lambda s: s.lower(),
    "upper": lambda s: s.upper(),
    "trim": lambda s: s.strip(),
    "ltrim": lambda s: s.lstrip(),
    "rtrim": lambda s: s.rstrip(),
    "substr": _py_substr,
    "substring": _py_substr,
    "concat": lambda s, suffix: s + suffix,
    "replace": lambda s, find, repl="": s.replace(find, repl),
    "reverse": lambda s: s[::-1],
    "lpad": lambda s, size, pad=" ": _py_pad(s, size, pad, True),
    "rpad": lambda s, size, pad=" ": _py_pad(s, size, pad, False),
    "split_part": _py_split_part,
    "regexp_replace": _py_regexp_replace,
    "regexp_extract": _py_regexp_extract,
    "concat_ws": lambda sep, *vals: sep.join(vals),
}

# transforms that may yield NULL per input value (carry an ok-table)
_NULLABLE_STRING_FNS = {"split_part", "regexp_extract"}


def _eval_special(expr: SpecialForm, page: Page, params=()) -> Column:
    kind = expr.kind
    if kind is SpecialKind.AND:
        return _kleene_and([_eval(a, page, params) for a in expr.args],
                           expr.type)
    if kind is SpecialKind.OR:
        return _kleene_or([_eval(a, page, params) for a in expr.args],
                          expr.type)
    if kind is SpecialKind.NOT:
        a = _eval(expr.args[0], page, params)
        return Column(~a.values, a.valid, expr.type, None)
    if kind is SpecialKind.IS_NULL:
        a = _eval(expr.args[0], page, params)
        if a.valid is None:
            vals = jnp.zeros(jnp.shape(a.values), dtype=jnp.bool_)
        else:
            vals = ~a.valid
        return Column(vals, None, expr.type, None)
    if kind is SpecialKind.COALESCE:
        args = [_eval(a, page, params) for a in expr.args]
        # content-equal pools dedup to one set element (fingerprint hash)
        dicts = {a.dictionary for a in args if a.dictionary is not None}
        if len(dicts) > 1:
            raise NotImplementedError("COALESCE over distinct dictionaries")
        dictionary = next((a.dictionary for a in args
                           if a.dictionary is not None), None)
        out = args[-1]
        for a in reversed(args[:-1]):
            if a.valid is None:
                out = a
                continue
            values = jnp.where(a.valid, a.values, out.values)
            valid = a.valid | out.valid if out.valid is not None else None
            out = Column(values, valid, expr.type, dictionary)
        return out
    if kind is SpecialKind.IF:
        return _if_merge(_eval(expr.args[0], page, params),
                         _eval(expr.args[1], page, params),
                         _eval(expr.args[2], page, params), expr.type)
    if kind is SpecialKind.SWITCH:
        # [c1, v1, c2, v2, ..., default] — fold right into nested IFs so CASE
        # shares IF's null/dictionary semantics exactly
        args = list(expr.args)
        out = _eval(args[-1], page, params)
        pairs = list(zip(args[:-1:2], args[1:-1:2]))
        for cond_e, val_e in reversed(pairs):
            out = _if_merge(_eval(cond_e, page, params),
                            _eval(val_e, page, params), out,
                            expr.type)
        return out
    if kind is SpecialKind.IN:
        needle = expr.args[0]
        eqs = [Call("eq", (needle, v), T.BOOLEAN) for v in expr.args[1:]]
        return _kleene_or([_eval(e, page, params) for e in eqs], expr.type)
    if kind is SpecialKind.BETWEEN:
        value, low, high = expr.args
        conj = SpecialForm(SpecialKind.AND, (
            Call("ge", (value, low), T.BOOLEAN),
            Call("le", (value, high), T.BOOLEAN)), T.BOOLEAN)
        return _eval(conj, page, params)
    raise TypeError(f"unknown special form: {kind}")


def _if_merge(cond: Column, then: Column, els: Column, out_type) -> Column:
    """IF(cond, then, els) null semantics: null condition selects else."""
    take_then = cond.values
    if cond.valid is not None:
        take_then = take_then & cond.valid
    if (then.dictionary is not None and els.dictionary is not None
            and then.dictionary != els.dictionary):
        # distinct string pools (e.g. CASE emitting literals): union the
        # pools at trace time and remap both sides' codes
        then, els = _merge_dictionaries(then, els)
    values = jnp.where(take_then, then.values, els.values)
    if then.valid is None and els.valid is None:
        valid = None
    else:
        tv = then.valid if then.valid is not None else jnp.ones((), jnp.bool_)
        ev = els.valid if els.valid is not None else jnp.ones((), jnp.bool_)
        valid = jnp.where(take_then, tv, ev)
    dictionary = then.dictionary if then.dictionary is not None \
        else els.dictionary
    return Column(values, valid, out_type, dictionary)


def _merge_dictionaries(a: Column, b: Column):
    """Rebase two dictionary columns onto one union pool (host-side, static)."""
    from trino_tpu.page import union_dictionaries
    merged, (ra, rb) = union_dictionaries([a.dictionary, b.dictionary])
    a2 = Column(jnp.take(ra, a.values, mode="clip"), a.valid, a.type, merged)
    b2 = Column(jnp.take(rb, b.values, mode="clip"), b.valid, b.type, merged)
    return a2, b2


def _kleene_and(args, out_type) -> Column:
    # false dominates null; null & true = null
    value, valid = args[0].values, args[0].valid
    for a in args[1:]:
        av, an = a.values, a.valid
        new_value = value & av
        if valid is None and an is None:
            new_valid = None
        else:
            v1 = valid if valid is not None else jnp.ones((), jnp.bool_)
            v2 = an if an is not None else jnp.ones((), jnp.bool_)
            # valid iff both valid, or either side is a definite false
            new_valid = (v1 & v2) | (v1 & ~value) | (v2 & ~av)
        value, valid = new_value, new_valid
    return Column(value, valid, out_type, None)


def _kleene_or(args, out_type) -> Column:
    value, valid = args[0].values, args[0].valid
    for a in args[1:]:
        av, an = a.values, a.valid
        new_value = value | av
        if valid is None and an is None:
            new_valid = None
        else:
            v1 = valid if valid is not None else jnp.ones((), jnp.bool_)
            v2 = an if an is not None else jnp.ones((), jnp.bool_)
            # valid iff both valid, or either side is a definite true
            new_valid = (v1 & v2) | (v1 & value) | (v2 & av)
        value, valid = new_value, new_valid
    return Column(value, valid, out_type, None)


def _broadcast(col: Column, capacity: int) -> Column:
    if jnp.ndim(col.values) == 0:
        values = jnp.broadcast_to(col.values, (capacity,))
        valid = col.valid
        if valid is not None and jnp.ndim(valid) == 0:
            valid = jnp.broadcast_to(valid, (capacity,))
        return Column(values, valid, col.type, col.dictionary)
    if col.valid is not None and jnp.ndim(col.valid) == 0:
        return Column(col.values, jnp.broadcast_to(col.valid, (capacity,)),
                      col.type, col.dictionary)
    return col


def compile_expression(expr: RowExpression) -> Callable[..., Column]:
    """Build fn(page, params=()) -> Column of per-row results (project
    channel). `params` is the positional scalar tuple Param leaves index
    into — () for unhoisted trees."""

    def fn(page: Page, params=()) -> Column:
        return _broadcast(_eval(expr, page, params), page.capacity)

    return fn


def compile_filter(expr: RowExpression) -> Callable[..., jnp.ndarray]:
    """Build fn(page, params=()) -> bool mask; SQL WHERE: null counts as
    false."""

    def fn(page: Page, params=()) -> jnp.ndarray:
        col = _broadcast(_eval(expr, page, params), page.capacity)
        mask = col.values
        if col.valid is not None:
            mask = mask & col.valid
        return mask

    return fn
