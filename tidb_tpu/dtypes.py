"""SQL type system mapped to TPU-friendly physical representations.

Reference: pkg/types (Datum pkg/types/datum.go:66, MyDecimal
pkg/types/mydecimal.go:236, Time/Duration, FieldType coercion). We keep the
logical SQL types but choose physical representations that XLA tiles well:

| SQL type      | device representation                                    |
|---------------|----------------------------------------------------------|
| BIGINT        | int64                                                    |
| DOUBLE        | float64 (x64 enabled; TPU computes f64 via passes)       |
| BOOLEAN       | bool                                                     |
| DATE          | int32 days since 1970-01-01                              |
| DECIMAL(p,s)  | scaled int64 (value * 10^s) — SF100 SUMs fit in i64 when |
|               | accumulated as f64/i64 pairs; see aggregate.py           |
| VARCHAR/CHAR  | int32 dictionary code; dictionary is sorted so code      |
|               | order == lexicographic (utf8mb4_bin) order               |

Every column carries a validity mask (True = not NULL), the reference's
null bitmap (pkg/util/chunk/column.go:63).
"""

from __future__ import annotations

import dataclasses
import enum
import re
from typing import Optional

import numpy as np


class Kind(enum.Enum):
    INT = "int"
    FLOAT = "float"
    BOOL = "bool"
    DATE = "date"
    # DATETIME/TIMESTAMP: int64 microseconds since the unix epoch (the
    # reference packs year..microsecond into a uint64 coreTime,
    # pkg/types/time.go; a flat micro count is the TPU-friendly layout —
    # comparisons, sorts, and interval arithmetic are plain int64 ops)
    DATETIME = "datetime"
    # TIME (duration): int64 microseconds, signed (pkg/types Duration)
    TIME = "time"
    DECIMAL = "decimal"
    STRING = "string"
    NULL = "null"  # type of bare NULL literal before coercion


@dataclasses.dataclass(frozen=True)
class SQLType:
    kind: Kind
    # decimal scale (digits after the point); 0 for non-decimals.
    scale: int = 0
    # STRING columns: collation name, or None = binary (the native
    # dictionary order). compare=False: collation affects COMPARISON
    # semantics, not type identity — INT64 == INT64 regardless
    # (reference: pkg/util/collate/collate.go Collator per column).
    collation: Optional[str] = dataclasses.field(
        default=None, compare=False
    )

    @property
    def np_dtype(self) -> np.dtype:
        return {
            Kind.INT: np.dtype(np.int64),
            Kind.FLOAT: np.dtype(np.float64),
            Kind.BOOL: np.dtype(np.bool_),
            Kind.DATE: np.dtype(np.int32),
            Kind.DATETIME: np.dtype(np.int64),
            Kind.TIME: np.dtype(np.int64),
            Kind.DECIMAL: np.dtype(np.int64),
            Kind.STRING: np.dtype(np.int32),
            Kind.NULL: np.dtype(np.int64),
        }[self.kind]

    @property
    def is_numeric(self) -> bool:
        return self.kind in (Kind.INT, Kind.FLOAT, Kind.DECIMAL, Kind.BOOL)

    @property
    def is_string(self) -> bool:
        return self.kind == Kind.STRING

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        if self.kind == Kind.DECIMAL:
            return f"DECIMAL(s={self.scale})"
        return self.kind.name


INT64 = SQLType(Kind.INT)
FLOAT64 = SQLType(Kind.FLOAT)
BOOL = SQLType(Kind.BOOL)
DATE = SQLType(Kind.DATE)
DATETIME = SQLType(Kind.DATETIME)
TIME = SQLType(Kind.TIME)
STRING = SQLType(Kind.STRING)
NULLTYPE = SQLType(Kind.NULL)

US_PER_DAY = 86_400_000_000
US_PER_SECOND = 1_000_000


def DECIMAL(scale: int) -> SQLType:
    return SQLType(Kind.DECIMAL, scale=scale)


def common_type(a: SQLType, b: SQLType) -> SQLType:
    """Result type of a binary arithmetic/comparison between a and b.

    Mirrors the reference's numeric coercion (pkg/expression type inference):
    FLOAT dominates; DECIMAL dominates INT; comparing decimals of different
    scale promotes to the larger scale.
    """
    if a.kind == Kind.NULL:
        return b
    if b.kind == Kind.NULL:
        return a
    if a == b:
        return a
    kinds = {a.kind, b.kind}
    if kinds == {Kind.DATE, Kind.DATETIME}:
        # comparing a DATE with a DATETIME promotes the date to midnight
        # (MySQL temporal comparison, pkg/types/time.go Compare)
        return DATETIME
    if Kind.FLOAT in kinds:
        return FLOAT64
    if Kind.DECIMAL in kinds:
        return DECIMAL(max(a.scale, b.scale))
    if kinds <= {Kind.INT, Kind.BOOL}:
        return INT64
    if Kind.DATE in kinds and Kind.INT in kinds:
        return INT64
    if Kind.DATETIME in kinds and Kind.INT in kinds:
        return INT64
    if Kind.TIME in kinds and Kind.INT in kinds:
        return INT64
    if Kind.STRING in kinds:
        # string vs numeric comparison: coerce via float (MySQL semantics),
        # handled at plan time; default here keeps the numeric side.
        return FLOAT64
    raise TypeError(f"no common type for {a} and {b}")


_RELAXED_DATE = re.compile(r"^\s*(\d{4})-(\d{1,2})-(\d{1,2})\s*$")


def date_to_days(s: str) -> int:
    """'YYYY-MM-DD' -> int32 days since epoch. MySQL's relaxed form, a
    month or day of one digit ('1999-2-01'), reads as the padded one."""
    m = _RELAXED_DATE.match(s) if isinstance(s, str) else None
    if m is not None:
        s = f"{m.group(1)}-{int(m.group(2)):02d}-{int(m.group(3)):02d}"
    return (np.datetime64(s, "D") - np.datetime64("1970-01-01", "D")).astype(int)


def days_to_date(d: int) -> str:
    return str(np.datetime64("1970-01-01", "D") + int(d))


def datetime_to_micros(s: str) -> int:
    """'YYYY-MM-DD[ HH:MM:SS[.ffffff]]' -> int64 microseconds since epoch."""
    s = s.strip().replace(" ", "T")
    if "T" not in s:
        s += "T00:00:00"
    return int(
        (np.datetime64(s, "us") - np.datetime64("1970-01-01T00:00:00", "us"))
        .astype(np.int64)
    )


def micros_to_datetime(us: int) -> str:
    """int64 micros -> 'YYYY-MM-DD HH:MM:SS[.ffffff]' (MySQL text form)."""
    dt = np.datetime64("1970-01-01T00:00:00", "us") + np.timedelta64(int(us), "us")
    txt = str(dt).replace("T", " ")
    if txt.endswith(".000000"):
        txt = txt[:-7]
    return txt


def time_to_micros(s: str) -> int:
    """'[-]HH:MM:SS[.ffffff]' -> signed int64 microseconds (Duration)."""
    s = s.strip()
    neg = s.startswith("-")
    if neg:
        s = s[1:]
    parts = s.split(":")
    if len(parts) == 2:
        parts = parts + ["0"]
    h, m = int(parts[0]), int(parts[1])
    sec = float(parts[2])
    us = ((h * 60 + m) * 60) * US_PER_SECOND + int(round(sec * US_PER_SECOND))
    return -us if neg else us


def micros_to_time(us: int) -> str:
    us = int(us)
    sign = "-" if us < 0 else ""
    us = abs(us)
    h, rem = divmod(us, 3600 * US_PER_SECOND)
    m, rem = divmod(rem, 60 * US_PER_SECOND)
    s, frac = divmod(rem, US_PER_SECOND)
    base = f"{sign}{h:02d}:{m:02d}:{s:02d}"
    return f"{base}.{frac:06d}" if frac else base
