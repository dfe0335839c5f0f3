"""Scalar function registry + builtin implementations.

Reference parity: operator/scalar/ (227 files) + sql/gen null-propagation
conventions. Implementations are jnp kernels over value arrays; the compiler
wraps them with default RETURNS NULL ON NULL INPUT semantics (valid = AND of
input valids), matching @ScalarFunction defaults.

Java-semantics notes (bit-identical goal, SURVEY §7 hard part 4):
- integer division/remainder truncate toward zero (lax.div/lax.rem), not
  Python floor semantics
- CAST(double AS bigint) rounds like Java Math.round: floor(x + 0.5)
- decimal arithmetic on scaled int64 with explicit rescaling, HALF_UP rounding

String functions run against the host-side Dictionary: a per-(dictionary, op)
lookup table is computed once on host and gathered by code on device — the
TPU-native replacement for per-row joni/re2j regex evaluation.
"""

from __future__ import annotations

import math
import re
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.page import Column, Dictionary

# ---------------------------------------------------------------------------
# registry

# impl(out_type, arg_types, *value_arrays) -> value_array
_SCALARS: Dict[str, Callable] = {}


def scalar(name: str):
    def deco(fn):
        _SCALARS[name] = fn
        return fn
    return deco


def lookup(name: str) -> Callable:
    if name not in _SCALARS:
        raise KeyError(f"unknown scalar function: {name}")
    return _SCALARS[name]


def exists(name: str) -> bool:
    return name in _SCALARS


# ---------------------------------------------------------------------------
# arithmetic

def _is_decimal(t):
    return isinstance(t, T.DecimalType)


def _rescale(values, from_scale: int, to_scale: int):
    """Scaled-int64 rescale with HALF_UP rounding on scale-down."""
    if to_scale == from_scale:
        return values
    if to_scale > from_scale:
        return values * (10 ** (to_scale - from_scale))
    factor = 10 ** (from_scale - to_scale)
    # round half away from zero, like Trino's Decimals HALF_UP
    half = factor // 2
    adj = jnp.where(values >= 0, values + half, values - half)
    return jax.lax.div(adj, jnp.int64(factor))


@scalar("add")
def _add(out_type, arg_types, a, b):
    if _is_decimal(out_type):
        a = _rescale(a, arg_types[0].scale, out_type.scale)
        b = _rescale(b, arg_types[1].scale, out_type.scale)
    return a + b


@scalar("subtract")
def _subtract(out_type, arg_types, a, b):
    if _is_decimal(out_type):
        a = _rescale(a, arg_types[0].scale, out_type.scale)
        b = _rescale(b, arg_types[1].scale, out_type.scale)
    return a - b


@scalar("multiply")
def _multiply(out_type, arg_types, a, b):
    if _is_decimal(out_type):
        raw = a * b  # scale = s1 + s2
        return _rescale(raw, arg_types[0].scale + arg_types[1].scale,
                        out_type.scale)
    return a * b


@scalar("divide")
def _divide(out_type, arg_types, a, b):
    if _is_decimal(out_type):
        # scale so ONE integer division + ONE HALF_UP rounding yields
        # out_type.scale exactly (no double rounding): shift the numerator up
        # when the target scale is higher, the denominator up when lower
        shift = out_type.scale + arg_types[1].scale - arg_types[0].scale
        num = a * (10 ** max(shift, 0)) if shift >= 0 else a
        den = b * (10 ** max(-shift, 0)) if shift < 0 else b
        half = jax.lax.div(jnp.abs(den), jnp.int64(2))
        adj = jnp.where((num >= 0) == (den >= 0), num + half, num - half)
        return jax.lax.div(adj, den)
    if jnp.issubdtype(jnp.result_type(a), jnp.integer):
        a, b = _promote_pair(a, b)
        return jax.lax.div(a, b)  # truncate toward zero (Java)
    return a / b


def _promote_pair(a, b):
    """lax.div/rem require identical dtypes; mixed-width integer operands
    (bigint % integer literal) promote to the common type first."""
    dt = jnp.result_type(a, b)
    return jnp.asarray(a).astype(dt), jnp.asarray(b).astype(dt)


@scalar("modulus")
def _modulus(out_type, arg_types, a, b):
    if jnp.issubdtype(jnp.result_type(a), jnp.integer):
        a, b = _promote_pair(a, b)
        return jax.lax.rem(a, b)  # sign of dividend (Java %)
    return jnp.fmod(a, b)


@scalar("negate")
def _negate(out_type, arg_types, a):
    return -a


# ------------------------------------------------------- bitwise / buckets
# Reference: operator/scalar/BitwiseFunctions.java, MathFunctions.java
# widthBucket

@scalar("bitwise_and")
def _bitand(out_type, arg_types, a, b):
    return a.astype(jnp.int64) & jnp.asarray(b).astype(jnp.int64)


@scalar("bitwise_or")
def _bitor(out_type, arg_types, a, b):
    return a.astype(jnp.int64) | jnp.asarray(b).astype(jnp.int64)


@scalar("bitwise_xor")
def _bitxor(out_type, arg_types, a, b):
    return a.astype(jnp.int64) ^ jnp.asarray(b).astype(jnp.int64)


@scalar("bitwise_not")
def _bitnot(out_type, arg_types, a):
    return ~a.astype(jnp.int64)


@scalar("bitwise_left_shift")
def _bitshl(out_type, arg_types, a, b):
    return a.astype(jnp.int64) << jnp.asarray(b).astype(jnp.int64)


@scalar("bitwise_right_shift")
def _bitshr(out_type, arg_types, a, b):
    # logical shift (Trino bitwise_right_shift zero-fills)
    ua = jax.lax.bitcast_convert_type(a.astype(jnp.int64), jnp.uint64)
    out = ua >> jnp.asarray(b).astype(jnp.uint64)
    return jax.lax.bitcast_convert_type(out, jnp.int64)


@scalar("bitwise_right_shift_arithmetic")
def _bitsar(out_type, arg_types, a, b):
    return a.astype(jnp.int64) >> jnp.asarray(b).astype(jnp.int64)


@scalar("bit_count")
def _bit_count(out_type, arg_types, a, bits):
    """Deviation: values not representable in `bits` MASK to the low bits
    (Trino raises); jit kernels cannot raise per-row — same policy as the
    div-by-zero garbage-not-error note."""
    u = jax.lax.bitcast_convert_type(a.astype(jnp.int64), jnp.uint64)
    mask = jnp.where(jnp.asarray(bits).astype(jnp.uint64) >= 64,
                     jnp.uint64(0xFFFFFFFFFFFFFFFF),
                     (jnp.uint64(1) << jnp.asarray(bits).astype(jnp.uint64))
                     - 1)
    return jax.lax.population_count(u & mask).astype(jnp.int64)


@scalar("width_bucket")
def _width_bucket(out_type, arg_types, x, lo, hi, n):
    x = x.astype(jnp.float64)
    lo = jnp.asarray(lo).astype(jnp.float64)
    hi = jnp.asarray(hi).astype(jnp.float64)
    n = jnp.asarray(n).astype(jnp.int64)
    b = jnp.floor((x - lo) / (hi - lo) * n.astype(jnp.float64)) + 1
    b = jnp.clip(b, 0, (n + 1).astype(jnp.float64))
    return b.astype(jnp.int64)


# ---------------------------------------------------------------------------
# comparison (numeric / date / codes — string literals are pre-folded to codes
# by the compiler using the column dictionary)

@scalar("eq")
def _eq(out_type, arg_types, a, b):
    return a == b


@scalar("ne")
def _ne(out_type, arg_types, a, b):
    return a != b


@scalar("lt")
def _lt(out_type, arg_types, a, b):
    return a < b


@scalar("le")
def _le(out_type, arg_types, a, b):
    return a <= b


@scalar("gt")
def _gt(out_type, arg_types, a, b):
    return a > b


@scalar("ge")
def _ge(out_type, arg_types, a, b):
    return a >= b


# ---------------------------------------------------------------------------
# math

@scalar("abs")
def _abs(out_type, arg_types, a):
    return jnp.abs(a)


@scalar("ceil")
def _ceil(out_type, arg_types, a):
    if _is_decimal(arg_types[0]):
        s = arg_types[0].scale
        f = jnp.int64(10 ** s)
        q = jax.lax.div(a, f)
        return q + ((jax.lax.rem(a, f) > 0) & (a > 0)).astype(jnp.int64)
    if jnp.issubdtype(jnp.result_type(a), jnp.integer):
        return a
    return jnp.ceil(a)


@scalar("floor")
def _floor(out_type, arg_types, a):
    if _is_decimal(arg_types[0]):
        s = arg_types[0].scale
        f = jnp.int64(10 ** s)
        q = jax.lax.div(a, f)
        return q - ((jax.lax.rem(a, f) < 0) & (a < 0)).astype(jnp.int64)
    if jnp.issubdtype(jnp.result_type(a), jnp.integer):
        return a
    return jnp.floor(a)


@scalar("round")
def _round(out_type, arg_types, a):
    if _is_decimal(arg_types[0]):
        return _rescale(a, arg_types[0].scale, 0)
    if jnp.issubdtype(jnp.result_type(a), jnp.integer):
        return a
    # Trino rounds half away from zero
    return jnp.where(a >= 0, jnp.floor(a + 0.5), jnp.ceil(a - 0.5))


@scalar("round_digits")
def _round_digits(out_type, arg_types, a, d):
    """round(x, d); the compiler folds literal d (the only supported form)."""
    if _is_decimal(arg_types[0]):
        # HALF_UP at digit d within the scaled-int representation; d may
        # arrive as a traced scalar (projected literal), so stay in jnp
        scale = arg_types[0].scale
        keep = jnp.asarray(d).astype(jnp.int64)
        step = jnp.power(jnp.int64(10),
                         jnp.clip(scale - keep, 0, 17)).astype(jnp.int64)
        half = step // 2
        mag = (jnp.abs(a) + half) // step * step
        rounded = jnp.where(a >= 0, mag, -mag).astype(jnp.int64)
        return jnp.where(keep >= scale, a, rounded)
    if jnp.issubdtype(jnp.result_type(a), jnp.integer):
        # Trino round(123, -1) = 120, half away from zero in integer space;
        # divide magnitudes so // (floor) acts as truncation toward zero.
        # Stay in jnp throughout: d arrives as a traced scalar (hoisted
        # literal, or a plain constant under the chain kernel's trace), so
        # Python `if d >= 0` control flow would fail at trace time
        keep = jnp.asarray(d).astype(jnp.int64)
        p = jnp.power(jnp.int64(10),
                      jnp.clip(-keep, 0, 17)).astype(jnp.int64)
        half = p // 2
        mag = (jnp.abs(a) + half) // p * p
        rounded = jnp.where(a >= 0, mag, -mag).astype(a.dtype)
        return jnp.where(keep >= 0, a, rounded)
    f = 10.0 ** d
    scaled = a * f
    return jnp.where(scaled >= 0, jnp.floor(scaled + 0.5),
                     jnp.ceil(scaled - 0.5)) / f


@scalar("sqrt")
def _sqrt(out_type, arg_types, a):
    return jnp.sqrt(a)


@scalar("power")
def _power(out_type, arg_types, a, b):
    return jnp.power(a, b)


@scalar("exp")
def _exp(out_type, arg_types, a):
    return jnp.exp(a)


@scalar("ln")
def _ln(out_type, arg_types, a):
    return jnp.log(a)


@scalar("log10")
def _log10(out_type, arg_types, a):
    return jnp.log10(a)


@scalar("cbrt")
def _cbrt(out_type, arg_types, a):
    return jnp.cbrt(a.astype(jnp.float64))


@scalar("log2")
def _log2(out_type, arg_types, a):
    return jnp.log2(a.astype(jnp.float64))


@scalar("log")
def _log(out_type, arg_types, b, x):
    # Trino log(b, x) = ln(x) / ln(b)
    return jnp.log(x.astype(jnp.float64)) / jnp.log(b.astype(jnp.float64))


@scalar("radians")
def _radians(out_type, arg_types, a):
    return jnp.deg2rad(a.astype(jnp.float64))


@scalar("degrees")
def _degrees(out_type, arg_types, a):
    return jnp.rad2deg(a.astype(jnp.float64))


for _trig, _jfn in (("sin", jnp.sin), ("cos", jnp.cos), ("tan", jnp.tan),
                    ("asin", jnp.arcsin), ("acos", jnp.arccos),
                    ("atan", jnp.arctan), ("sinh", jnp.sinh),
                    ("cosh", jnp.cosh), ("tanh", jnp.tanh)):
    def _mk(jfn):
        def impl(out_type, arg_types, a):
            return jfn(a.astype(jnp.float64))
        return impl
    _SCALARS[_trig] = _mk(_jfn)


@scalar("atan2")
def _atan2(out_type, arg_types, a, b):
    return jnp.arctan2(a.astype(jnp.float64), b.astype(jnp.float64))


@scalar("pi")
def _pi(out_type, arg_types):
    return jnp.asarray(math.pi, dtype=jnp.float64)


@scalar("e")
def _e(out_type, arg_types):
    return jnp.asarray(math.e, dtype=jnp.float64)


@scalar("truncate")
def _truncate(out_type, arg_types, a, n=None):
    # MathFunctions.java truncate: drop the fractional part toward zero;
    # two-arg form truncates to n decimal places
    a = a.astype(jnp.float64)
    if n is None:
        return jnp.trunc(a)
    factor = 10.0 ** n.astype(jnp.float64)
    return jnp.trunc(a * factor) / factor


@scalar("sign")
def _sign(out_type, arg_types, a):
    return jnp.sign(a)


@scalar("greatest")
def _greatest(out_type, arg_types, *args):
    out = args[0]
    for a in args[1:]:
        out = jnp.maximum(out, a)
    return out


@scalar("least")
def _least(out_type, arg_types, *args):
    out = args[0]
    for a in args[1:]:
        out = jnp.minimum(out, a)
    return out


# ---------------------------------------------------------------------------
# date/time. DATE = int32 days since epoch; civil-date math in pure integer
# ops (vectorizes onto VPU; reference: scalar/DateTimeFunctions.java).

def _civil_from_days(days):
    """days since 1970-01-01 -> (year, month, day), proleptic Gregorian.

    The arithmetic runs in int32 — every intermediate of a representable
    date fits — and the results widen to int64 for the callers. The TPU
    emulates 64-bit integer division op by op: as int64, the fourteen
    scalar divides of one `date + interval year` made q6's fused chain an
    8 MB program that took the v5e compiler 22 s and 8 s more to load
    back from the compile cache (PR 23)."""
    i32 = jnp.int32
    z = days.astype(i32) + i32(719468)
    era = jax.lax.div(jnp.where(z >= 0, z, z - i32(146096)), i32(146097))
    doe = z - era * i32(146097)
    yoe = jax.lax.div(
        doe - jax.lax.div(doe, i32(1460))
        + jax.lax.div(doe, i32(36524))
        - jax.lax.div(doe, i32(146096)), i32(365))
    y = yoe + era * i32(400)
    doy = doe - (i32(365) * yoe + jax.lax.div(yoe, i32(4))
                 - jax.lax.div(yoe, i32(100)))
    mp = jax.lax.div(i32(5) * doy + i32(2), i32(153))
    d = doy - jax.lax.div(i32(153) * mp + i32(2), i32(5)) + i32(1)
    m = mp + jnp.where(mp < 10, i32(3), i32(-9))
    y = y + (m <= 2).astype(i32)
    return (y.astype(jnp.int64), m.astype(jnp.int64),
            d.astype(jnp.int64))


def days_from_civil(y: int, m: int, d: int) -> int:
    """Host-side inverse (for literals/boundaries)."""
    y -= m <= 2
    era = (y if y >= 0 else y - 399) // 400
    yoe = y - era * 400
    doy = (153 * (m + (-3 if m > 2 else 9)) + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


@scalar("year")
def _year(out_type, arg_types, a):
    y, _, _ = _civil_from_days(_days_of(arg_types[0], a))
    return y


@scalar("month")
def _month(out_type, arg_types, a):
    _, m, _ = _civil_from_days(_days_of(arg_types[0], a))
    return m


@scalar("day")
def _day(out_type, arg_types, a):
    _, _, d = _civil_from_days(_days_of(arg_types[0], a))
    return d


@scalar("quarter")
def _quarter(out_type, arg_types, a):
    _, m, _ = _civil_from_days(_days_of(arg_types[0], a))
    return jax.lax.div(m - 1, jnp.int64(3)) + 1


def _days_of(typ, a):
    if isinstance(typ, T.DateType):
        return a
    if isinstance(typ, T.TimestampType):
        micros_per_day = jnp.int64(86_400_000_000)
        return jax.lax.div(
            jnp.where(a >= 0, a, a - micros_per_day + 1), micros_per_day)
    raise TypeError(f"not a temporal type: {typ}")


def _add_months_device(days, months):
    """date + interval year-month with end-of-month clamping (int32
    arithmetic, see _civil_from_days)."""
    i32 = jnp.int32
    y, m, d = (x.astype(i32) for x in _civil_from_days(days))
    total = y * i32(12) + (m - i32(1)) + jnp.asarray(months).astype(i32)
    ny = jax.lax.div(jnp.where(total >= 0, total, total - i32(11)), i32(12))
    nm = total - ny * i32(12) + i32(1)
    # clamp day to target month length
    leap = ((jax.lax.rem(ny, i32(4)) == 0)
            & (jax.lax.rem(ny, i32(100)) != 0)
            | (jax.lax.rem(ny, i32(400)) == 0))
    mlen = jnp.array([31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31],
                     dtype=i32)
    length = mlen[nm - i32(1)] + ((nm == 2) & leap).astype(i32)
    nd = jnp.minimum(d, length)
    return _days_from_civil_device(ny, nm, nd).astype(i32)


@scalar("day_of_week")
def _day_of_week(out_type, arg_types, a):
    # ISO: 1 = Monday .. 7 = Sunday (1970-01-01 was a Thursday, days=0 -> 4)
    days = _days_of(arg_types[0], a).astype(jnp.int64)
    return jax.lax.rem(jax.lax.rem(days + 3, jnp.int64(7)) + 7,
                       jnp.int64(7)) + 1


def _trunc_year_days(days):
    y, _, _ = _civil_from_days(days)
    return _days_from_civil_device(y, jnp.int64(1), jnp.int64(1))


def _days_from_civil_device(y, m, d):
    """(year, month, day) -> int64 days since epoch (int32 arithmetic
    inside, see _civil_from_days)."""
    i32 = jnp.int32
    y, m, d = (jnp.asarray(x).astype(i32) for x in (y, m, d))
    yy = y - (m <= 2).astype(i32)
    era = jax.lax.div(jnp.where(yy >= 0, yy, yy - i32(399)), i32(400))
    yoe = yy - era * i32(400)
    doy = jax.lax.div(
        i32(153) * (m + jnp.where(m > 2, i32(-3), i32(9))) + i32(2),
        i32(5)) + d - i32(1)
    doe = yoe * i32(365) + jax.lax.div(yoe, i32(4)) - jax.lax.div(
        yoe, i32(100)) + doy
    return (era * i32(146097) + doe - i32(719468)).astype(jnp.int64)


@scalar("day_of_year")
def _day_of_year(out_type, arg_types, a):
    days = _days_of(arg_types[0], a).astype(jnp.int64)
    return days - _trunc_year_days(days) + 1


@scalar("week")
def _week(out_type, arg_types, a):
    # ISO 8601 week-of-year: the week containing this date's Thursday
    days = _days_of(arg_types[0], a).astype(jnp.int64)
    dow0 = jax.lax.rem(jax.lax.rem(days + 3, jnp.int64(7)) + 7,
                       jnp.int64(7))          # 0 = Monday
    thursday = days - dow0 + 3
    return jax.lax.div(thursday - _trunc_year_days(thursday),
                       jnp.int64(7)) + 1


@scalar("last_day_of_month")
def _last_day_of_month(out_type, arg_types, a):
    days = _days_of(arg_types[0], a).astype(jnp.int64)
    y, m, _ = _civil_from_days(days)
    nxt_m = jnp.where(m == 12, 1, m + 1)
    nxt_y = jnp.where(m == 12, y + 1, y)
    return (_days_from_civil_device(nxt_y, nxt_m, jnp.int64(1)) - 1) \
        .astype(jnp.int32)


def date_trunc_days(unit: str, days):
    """DATE date_trunc (DateTimeFunctions.java truncateDate analog)."""
    days = days.astype(jnp.int64)
    if unit == "day":
        return days.astype(jnp.int32)
    if unit == "week":
        dow0 = jax.lax.rem(jax.lax.rem(days + 3, jnp.int64(7)) + 7,
                           jnp.int64(7))
        return (days - dow0).astype(jnp.int32)
    y, m, _ = _civil_from_days(days)
    if unit == "month":
        return _days_from_civil_device(y, m, jnp.int64(1)).astype(jnp.int32)
    if unit == "quarter":
        qm = (jax.lax.div(m - 1, jnp.int64(3))) * 3 + 1
        return _days_from_civil_device(y, qm, jnp.int64(1)).astype(jnp.int32)
    if unit == "year":
        return _days_from_civil_device(y, jnp.int64(1),
                                       jnp.int64(1)).astype(jnp.int32)
    raise NotImplementedError(f"date_trunc unit {unit!r} on DATE")


def date_diff_days(unit: str, a, b):
    """date_diff(unit, a, b) = b - a in whole units (DateTimeFunctions
    diffDate analog: LocalDate.until semantics for month/year)."""
    a = a.astype(jnp.int64)
    b = b.astype(jnp.int64)
    if unit == "day":
        return b - a
    if unit == "week":
        # ChronoUnit.WEEKS.between: whole weeks, truncated toward zero
        return jax.lax.div(b - a, jnp.int64(7))
    if unit in ("month", "quarter", "year"):
        ay, am, ad = _civil_from_days(a)
        by, bm, bd = _civil_from_days(b)
        months = (by - ay) * 12 + (bm - am)
        # not a full month yet if the day-of-month hasn't been reached
        months = months - jnp.where((months > 0) & (bd < ad), 1, 0)
        months = months + jnp.where((months < 0) & (bd > ad), 1, 0)
        if unit == "month":
            return months
        div = 3 if unit == "quarter" else 12
        q = jax.lax.div(months, jnp.int64(div))
        return q
    raise NotImplementedError(f"date_diff unit {unit!r} on DATE")


def date_add_days(unit: str, n, days):
    if unit == "day":
        return (days + n).astype(jnp.int32)
    if unit == "week":
        return (days + 7 * n).astype(jnp.int32)
    if unit == "month":
        return _add_months_device(days, n)
    if unit == "quarter":
        return _add_months_device(days, 3 * n)
    if unit == "year":
        return _add_months_device(days, 12 * n)
    raise NotImplementedError(f"date_add unit {unit!r} on DATE")


@scalar("date_add_ym")
def _date_add_ym(out_type, arg_types, days, months):
    return _add_months_device(days, months)


@scalar("date_add_dt")
def _date_add_dt(out_type, arg_types, days, micros):
    micros_per_day = jnp.int64(86_400_000_000)
    return (days + jax.lax.div(micros, micros_per_day)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# casts

@scalar("cast")
def _cast(out_type, arg_types, a):
    src = arg_types[0]
    if src == out_type:
        return a
    if isinstance(out_type, T.DoubleType):
        if _is_decimal(src):
            return a.astype(jnp.float64) / (10.0 ** src.scale)
        return a.astype(jnp.float64)
    if isinstance(out_type, T.RealType):
        if _is_decimal(src):
            return (a.astype(jnp.float64) / (10.0 ** src.scale)).astype(jnp.float32)
        return a.astype(jnp.float32)
    if isinstance(out_type, (T.BigintType, T.IntegerType, T.SmallintType,
                             T.TinyintType)):
        if isinstance(src, (T.DoubleType, T.RealType)):
            # Java Math.round semantics: floor(x + 0.5)
            return jnp.floor(a.astype(jnp.float64) + 0.5).astype(out_type.dtype)
        if _is_decimal(src):
            return _rescale(a, src.scale, 0).astype(out_type.dtype)
        return a.astype(out_type.dtype)
    if _is_decimal(out_type):
        if _is_decimal(src):
            return _rescale(a, src.scale, out_type.scale)
        if isinstance(src, (T.DoubleType, T.RealType)):
            scaled = a.astype(jnp.float64) * (10.0 ** out_type.scale)
            return jnp.floor(scaled + jnp.where(scaled >= 0, 0.5, -0.5)).astype(jnp.int64)
        if T.is_integral(src):
            return a.astype(jnp.int64) * (10 ** out_type.scale)
    if isinstance(out_type, T.TimestampType) and isinstance(src, T.DateType):
        return a.astype(jnp.int64) * 86_400_000_000
    if isinstance(out_type, T.DateType) and isinstance(src, T.TimestampType):
        return _days_of(src, a).astype(jnp.int32)
    if isinstance(out_type, T.BooleanType):
        return a != 0
    if isinstance(src, T.BooleanType) and T.is_numeric(out_type):
        return a.astype(out_type.dtype)
    raise NotImplementedError(f"cast {src} -> {out_type}")


# ---------------------------------------------------------------------------
# dictionary-backed string ops: host computes a per-pool table, device gathers.

def _dict_cache(d: Dictionary) -> Dict:
    """Per-Dictionary memo table, living/dying with the pool object (so a
    long-running server that churns dictionaries never leaks device arrays)."""
    cache = getattr(d, "_table_cache", None)
    if cache is None:
        cache = {}
        object.__setattr__(d, "_table_cache", cache)
    return cache


def dictionary_table(d: Dictionary, key, fn) -> np.ndarray:
    """Memoized host map over the string pool, indexed by code.

    Cached as HOST numpy (jnp.asarray under an active jit trace would cache a
    tracer and poison later traces); jnp ops at the use sites embed it as a
    compile-time constant per trace.
    """
    cache = _dict_cache(d)
    if key not in cache:
        cache[key] = np.asarray([fn(s) for s in d.values])
    return cache[key]


def like_pattern_to_regex(pattern: str, escape: Optional[str] = None) -> str:
    out = []
    i = 0
    while i < len(pattern):
        c = pattern[i]
        if escape and c == escape and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 2
            continue
        if c == "%":
            out.append(".*")
        elif c == "_":
            out.append(".")
        else:
            out.append(re.escape(c))
        i += 1
    return "^" + "".join(out) + "$"


def like_matcher(pattern: str, escape: Optional[str] = None):
    """str -> whether it matches the LIKE pattern."""
    rx = re.compile(like_pattern_to_regex(pattern, escape), re.DOTALL)
    return lambda s: rx.match(s) is not None


def like_table(d: Dictionary, pattern: str,
               escape: Optional[str] = None) -> jnp.ndarray:
    return dictionary_table(d, ("like", pattern, escape),
                            like_matcher(pattern, escape))


def transform_dictionary_nullable(d: Dictionary, key, fn):
    """Like transform_dictionary but fn may return None (SQL NULL):
    (new dictionary, code remap, ok mask per input code)."""
    cache = _dict_cache(d)
    ck = (key, "xform-null")
    if ck not in cache:
        transformed = [fn(s) for s in d.values]
        ok = np.asarray([t is not None for t in transformed])
        vals = np.asarray(["" if t is None else t for t in transformed],
                          dtype=object)
        new_vals, remap = np.unique(vals, return_inverse=True)
        cache[ck] = (Dictionary(new_vals), remap.astype(np.int32), ok)
    return cache[ck]


def transform_dictionary(d: Dictionary, key, fn) -> Tuple[Dictionary, jnp.ndarray]:
    """str->str transform as (new sorted dictionary, code remap table).

    Device: new_codes = take(remap, codes). Memoized per (dictionary, op).
    """
    cache = _dict_cache(d)
    ck = (key, "xform")
    if ck not in cache:
        transformed = np.asarray([fn(s) for s in d.values], dtype=object)
        new_vals, remap = np.unique(transformed, return_inverse=True)
        nd = Dictionary(new_vals)
        # host numpy, not jnp: see dictionary_table
        cache[ck] = (nd, remap.astype(np.int32))
    return cache[ck]
