"""How the served path writes values on the wire, for the references."""

from __future__ import annotations

import numpy as np


def days(date: str) -> int:
    return int(np.datetime64(date, "D").astype(np.int64))


def date_text(day: int) -> str:
    return str(np.datetime64(int(day), "D"))


def dec(value: int, scale: int) -> str:
    """Scaled integer -> the wire's decimal text ('1227180.2380')."""
    sign, value = ("-", -value) if value < 0 else ("", value)
    return f"{sign}{value // 10 ** scale}.{value % 10 ** scale:0{scale}d}"


def avg(total: int, count: int) -> int:
    """Decimal avg keeps the input scale, rounding half up."""
    return (2 * total + count) // (2 * count)
