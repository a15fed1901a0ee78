"""Date formats of a `date` field's mapping and of a `range` query's
`format` (reference DateFormatter / DateFieldMapper, JavaDateMathParser's
rounding of a bound's missing parts).

A format is one or more patterns joined by `||`, tried in order: a named
one (`strict_date_optional_time`, `date_optional_time`, `epoch_millis`,
`epoch_second`, `strict_date` / `date`, `basic_date`,
`strict_date_hour_minute_second`, `strict_date_time`,
`strict_date_time_no_millis` and their non-strict names) or a Java-style
pattern of the letters `y u M d H m s S`, zone letters `X Z` and literals
(`dd/MM/yyyy`, `yyyy-MM-dd HH:mm:ss`). Anything else is an error that names
the pattern: no format falls back to another silently.

`parse_date(value, fmt, round_up)` -> epoch milliseconds (UTC where the
text carries no zone). The parts a text leaves out are filled with their
least value, or with their greatest where `round_up` is set: a `range`
query's `lte` / `gt` bound rounds up (`lte 21/01/2015` under `dd/MM/yyyy` is
2015-01-21T23:59:59.999Z), `gte` / `lt` down, and so does an indexed value.

`fmt` None is the mapping's default: ISO-8601 or epoch milliseconds (a
string of digits is milliseconds), then the few legacy `strptime` patterns
this engine has always read."""

from __future__ import annotations

import calendar
import datetime as _dt
import numbers
import re
from functools import lru_cache
from typing import Any, Callable, List, Optional, Tuple

_ISO_STRICT = re.compile(
    r"(?P<y>-?\d{4,9})(?:-(?P<M>\d{2})(?:-(?P<d>\d{2})"
    r"(?:T(?P<H>\d{2})(?::(?P<m>\d{2})(?::(?P<s>\d{2})"
    r"(?:[.,](?P<S>\d{1,9}))?)?)?(?P<z>Z|[+-]\d{2}(?::?\d{2})?)?)?)?)?$")
_ISO_LENIENT = re.compile(
    r"(?P<y>-?\d{1,9})(?:-(?P<M>\d{1,2})(?:-(?P<d>\d{1,2})"
    r"(?:[T ](?P<H>\d{1,2})(?::(?P<m>\d{1,2})(?::(?P<s>\d{1,2})"
    r"(?:[.,](?P<S>\d{1,9}))?)?)?(?P<z>Z|[+-]\d{2}(?::?\d{2})?)?)?)?)?$")
_EPOCH = re.compile(r"-?\d+(?:\.\d+)?$")

# named formats that are plain patterns
_NAMED_PATTERNS = {
    "date": "yyyy-MM-dd", "strict_date": "yyyy-MM-dd",
    "basic_date": "yyyyMMdd",
    "date_hour_minute_second": "yyyy-MM-dd'T'HH:mm:ss",
    "strict_date_hour_minute_second": "yyyy-MM-dd'T'HH:mm:ss",
    "date_time": "yyyy-MM-dd'T'HH:mm:ss.SSSXXX",
    "strict_date_time": "yyyy-MM-dd'T'HH:mm:ss.SSSXXX",
    "date_time_no_millis": "yyyy-MM-dd'T'HH:mm:ssXXX",
    "strict_date_time_no_millis": "yyyy-MM-dd'T'HH:mm:ssXXX",
}
_LEGACY_STRPTIME = ("%Y/%m/%d", "%Y/%m/%d %H:%M:%S", "%d-%m-%Y", "%m/%d/%Y")

Parser = Callable[[str, bool], Optional[int]]


class DateFormatError(ValueError):
    """A format this engine cannot read (a client error: 400)."""


def _millis(parts: dict, round_up: bool) -> Optional[int]:
    """Epoch ms of the regex groups y M d H m s S z; None where a part is
    out of its range. Missing parts take their least value, or their
    greatest where `round_up`."""
    y = int(parts["y"])
    if not 1 <= y <= 9999:
        return None
    M = int(parts["M"]) if parts.get("M") else (12 if round_up else 1)
    if not 1 <= M <= 12:
        return None
    last = calendar.monthrange(y, M)[1]
    d = int(parts["d"]) if parts.get("d") else (last if round_up else 1)
    H = int(parts["H"]) if parts.get("H") else (23 if round_up else 0)
    m = int(parts["m"]) if parts.get("m") else (59 if round_up else 0)
    s = int(parts["s"]) if parts.get("s") else (59 if round_up else 0)
    if not (1 <= d <= last and H <= 23 and m <= 59 and s <= 59):
        return None
    frac = parts.get("S")
    if frac:
        ms = int((frac + "00")[:3])
    else:
        ms = 999 if round_up else 0
    day = _dt.date(y, M, d).toordinal() - 719163      # days since 1970-01-01
    out = ((day * 24 + H) * 60 + m) * 60000 + s * 1000 + ms
    z = parts.get("z")
    if z and z != "Z":
        digits = z[1:].replace(":", "")
        off = int(digits[:2]) * 60 + int(digits[2:4] or 0)
        out -= (off if z[0] == "+" else -off) * 60000
    return out


def _regex_parser(rx) -> Parser:
    def parse(s: str, round_up: bool) -> Optional[int]:
        mm = rx.match(s)
        return _millis(mm.groupdict(), round_up) if mm else None
    return parse


def _epoch_parser(scale: int) -> Parser:
    def parse(s: str, _round_up: bool) -> Optional[int]:
        if not _EPOCH.match(s):
            return None
        return int(s) * scale if "." not in s else int(float(s) * scale)
    return parse


_LETTER_GROUP = {"y": "y", "u": "y", "M": "M", "d": "d", "H": "H", "m": "m",
                 "s": "s"}


def _pattern_parser(pattern: str, fmt: str) -> Parser:
    """A Java-style pattern as a regex over the groups `_millis` reads."""
    out, i, seen = [], 0, set()
    while i < len(pattern):
        c = pattern[i]
        if c == "'":
            j = pattern.find("'", i + 1)
            if j < 0:
                raise DateFormatError(
                    f"Invalid format: [{fmt}]: unterminated quote in "
                    f"pattern [{pattern}]")
            out.append(re.escape(pattern[i + 1: j] or "'"))
            i = j + 1
            continue
        if not c.isalpha():
            out.append(re.escape(c))
            i += 1
            continue
        j = i
        while j < len(pattern) and pattern[j] == c:
            j += 1
        n, i = j - i, j
        if c in _LETTER_GROUP and n <= (9 if c in "yu" else 2):
            g = _LETTER_GROUP[c]
            if g in seen:
                raise DateFormatError(
                    f"Invalid format: [{fmt}]: pattern [{pattern}] names "
                    f"[{c}] twice")
            seen.add(g)
            if g == "y":
                digits = r"\d{4}" if n == 4 else (r"\d{2}" if n == 2
                                                  else r"-?\d{1,9}")
                if n == 2:
                    g = "yy"
            else:
                digits = r"\d{2}" if n == 2 else r"\d{1,2}"
            out.append(f"(?P<{g}>{digits})")
        elif c == "S" and "S" not in seen:
            seen.add("S")
            out.append(f"(?P<S>\\d{{{n}}})")
        elif c in "XZ" and "z" not in seen:
            seen.add("z")
            out.append(r"(?P<z>Z|[+-]\d{2}(?::?\d{2})?)")
        else:
            raise DateFormatError(
                f"Invalid format: [{fmt}]: unknown pattern [{pattern}] "
                f"(letter [{c * n}]; this engine reads y u M d H m s S X Z, "
                f"quoted literals and the named formats)")
    if not seen & {"y", "yy"}:
        raise DateFormatError(f"Invalid format: [{fmt}]: pattern "
                              f"[{pattern}] has no year")
    rx = re.compile("".join(out) + "$")

    def parse(s: str, round_up: bool) -> Optional[int]:
        mm = rx.match(s)
        if not mm:
            return None
        parts = mm.groupdict()
        if parts.get("yy"):
            parts["y"] = str(2000 + int(parts["yy"]))
        return _millis(parts, round_up)
    return parse


@lru_cache(maxsize=256)
def compile_format(fmt: str) -> Tuple[Parser, ...]:
    """The parsers of `fmt`'s patterns, in order; DateFormatError naming the
    first pattern this engine does not read."""
    parsers: List[Parser] = []
    for part in (p.strip() for p in str(fmt).split("||")):
        if part == "epoch_millis":
            parsers.append(_epoch_parser(1))
        elif part == "epoch_second":
            parsers.append(_epoch_parser(1000))
        elif part == "strict_date_optional_time":
            parsers.append(_regex_parser(_ISO_STRICT))
        elif part == "date_optional_time":
            parsers.append(_regex_parser(_ISO_LENIENT))
        elif part in _NAMED_PATTERNS:
            parsers.append(_pattern_parser(_NAMED_PATTERNS[part], fmt))
        elif not part or re.fullmatch(r"[a-z_]+", part):
            # a name, and not one of ours (a pattern's letters repeat and
            # mix case: `yyyy`, `dd/MM/yyyy`)
            raise DateFormatError(f"Invalid format: [{fmt}]: unknown date "
                                  f"format [{part}]")
        else:
            parsers.append(_pattern_parser(part, fmt))
    return tuple(parsers)


def validate_format(fmt: Optional[str]) -> None:
    if fmt is not None:
        compile_format(fmt)


def _parse_default(s: str, round_up: bool) -> int:
    if s.isdigit() or (s[:1] == "-" and s[1:].isdigit()):
        return int(s)
    out = _regex_parser(_ISO_LENIENT)(s, round_up)
    if out is not None:
        return out
    try:
        dt = _dt.datetime.fromisoformat(s.replace("Z", "+00:00"))
    except ValueError:
        for f in _LEGACY_STRPTIME:
            try:
                dt = _dt.datetime.strptime(s, f)
                break
            except ValueError:
                continue
        else:
            raise ValueError(f"failed to parse date field [{s}]")
    if dt.tzinfo is None:
        dt = dt.replace(tzinfo=_dt.timezone.utc)
    return int(dt.timestamp() * 1000)


def parse_date(value: Any, fmt: Optional[str],
               round_up: bool = False) -> int:
    """`value` as epoch milliseconds under `fmt` (module docstring). A JSON
    number is epoch milliseconds, or seconds where the format reads
    `epoch_second` and not `epoch_millis`."""
    if isinstance(value, bool):
        raise ValueError(f"cannot parse date from boolean [{value}]")
    if fmt is None:
        if isinstance(value, numbers.Number):
            return int(value)
        return _parse_default(str(value).strip(), round_up)
    parsers = compile_format(fmt)
    if isinstance(value, numbers.Number):
        names = [p.strip() for p in fmt.split("||")]
        if "epoch_millis" in names:
            return int(value)
        if "epoch_second" in names:
            return int(value * 1000)
        value = repr(value) if isinstance(value, float) else str(value)
    s = str(value).strip()
    for parse in parsers:
        out = parse(s, round_up)
        if out is not None:
            return out
    raise ValueError(f"failed to parse date field [{s}] with format [{fmt}]")
