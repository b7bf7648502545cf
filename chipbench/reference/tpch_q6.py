"""Plain NumPy reference for TPC-H Q6 as the benchmark serves it.

Imports nothing of the program. Input is `Generator.live()` of
`chipbench/traffic/tpch_full.py` (table -> column name -> host values, i64;
dates are day numbers since 1992-01-01, `l_extendedprice` in cents,
`l_discount` a whole percent); output is `{"revenue": exact
integer at scale 4}` (cents x percent), or `{}` where no lineitem qualifies
(the view then serves one NULL row, which `parse` turns into `{}` too).
`parse` gives the same form for the one row that came over pgwire (text),
HTTP (JSON) or the SUBSCRIBE stream.

    SELECT sum(l_extendedprice * l_discount) AS revenue FROM lineitem
    WHERE l_shipdate >= DATE '1994-01-01' AND l_shipdate < DATE '1994-01-01' + INTERVAL '1' YEAR
      AND l_discount BETWEEN 0.06 - 0.01 AND 0.06 + 0.01 AND l_quantity < 24

The predicate is decided in integers: the discount bounds are 5 and 7
percent, the year is [1994-01-01, 1995-01-01) in the generator's calendar.
"""

from __future__ import annotations

from decimal import Decimal

import numpy as np

SCALE = 4  # fractional digits of the served answer: cents (scale 2) x percent (scale 2)
DISCOUNT, QUANTITY = 6, 24  # the specification's validation values: DISCOUNT 0.06 as a percent, QUANTITY


def _day(y: int, m: int, d: int) -> int:
    return int((np.datetime64(f"{y:04d}-{m:02d}-{d:02d}") - np.datetime64("1992-01-01")).astype(int))


SHIP_FROM, SHIP_TO = _day(1994, 1, 1), _day(1995, 1, 1)  # DATE '1994-01-01' and one year on


def q6(live: dict, dtype=np.int64) -> dict:
    """`{"revenue": sum of l_extendedprice * l_discount over the qualifying
    lineitems, at scale 4}`. `dtype` is the arithmetic's type: int64 is exact
    (some 3e12 at SF1), the control passes float32."""
    li = live["lineitem"]
    price, disc, ship, qty = li["l_extendedprice"], li["l_discount"], li["l_shipdate"], li["l_quantity"]
    hit = ((ship >= SHIP_FROM) & (ship < SHIP_TO) & (disc >= DISCOUNT - 1) & (disc <= DISCOUNT + 1)
           & (qty < QUANTITY))
    if not hit.any():
        return {}
    products = price[hit].astype(dtype) * disc[hit].astype(dtype)
    return {"revenue": int(products.sum(dtype=dtype))}


def _parse_q6(rows) -> dict:
    rows = list(rows)
    if len(rows) != 1:
        raise ValueError(f"Q6 serves one row, got {len(rows)}")
    (value,) = rows[0]
    if value is None or value in ("", "NULL", "\\N"):
        return {}
    scaled = Decimal(str(value)).scaleb(SCALE)
    if scaled != scaled.to_integral_value():
        raise ValueError(f"{value!r} has more than {SCALE} fractional digits")
    return {"revenue": int(scaled)}


# view name (as the configuration and workload files give it) -> (reference, parser of served rows)
VIEWS = {
    "q6": (q6, _parse_q6),
}


def differ(got, want) -> int:
    """How many answers differ between what was served and the reference:
    the one value missing, extra or another."""
    keys = set(got) | set(want)
    return sum(1 for k in keys if got.get(k) != want.get(k))
