"""Plain NumPy reference for TPC-H Q17 as the benchmark serves it.

Imports nothing of the program. Input is `Generator.live()` of
`chipbench/traffic/tpch_q17.py` (host columns, i64); output is
`{"avg_yearly": exact integer at scale 6}`, or `{}` where no lineitem
qualifies (the view then serves one NULL row, which `parse` turns into `{}`
too). `parse` gives the same form for the one row that came over pgwire
(text), HTTP (JSON) or the SUBSCRIBE stream.

    SELECT sum(l_extendedprice) / 7.0 FROM lineitem, part
    WHERE p_partkey = l_partkey AND p_brand = 7 AND p_container = 17
      AND l_quantity < (SELECT 0.2 * avg(l_quantity) FROM lineitem WHERE l_partkey = p_partkey)

The predicate is decided in integers: `q < 0.2 * s / n` is `5 * q * n < s`
over the part's integer sum `s` and count `n` of quantities. The division is
the program's documented NUMERIC rule (`materialize_tpu/sql/plan.py`, module
note): at least six fractional digits, truncated toward zero; the dividend is
cents (scale 2) and the divisor 7.0 (scale 1), so the answer at scale 6 is
`cents * 10**5 // 70`.
"""

from __future__ import annotations

from decimal import Decimal

import numpy as np

SCALE = 6  # fractional digits of the served answer (the program's NUMERIC division rule)
BRAND, CONTAINER = 7, 17  # 'Brand#23' and 'MED BOX' as the program's int64 codes


def part_filter(brand: np.ndarray, container: np.ndarray) -> np.ndarray:
    """Q17's published pair of constants, as a mask over `part`."""
    return (brand == BRAND) & (container == CONTAINER)


def q17(live: dict, dtype=np.int64, part_filter=part_filter) -> dict:
    """`{"avg_yearly": sum of qualifying l_extendedprice / 7.0 at scale 6}`.
    `dtype` is the arithmetic's type: int64 is exact, the control passes
    float32. `part_filter` stands for the two constants (a test passes a less
    selective pair of the same form)."""
    pk, brand, container = live["part"]
    lpk, price, qty = live["lineitem"][5], live["lineitem"][1], live["lineitem"][4]
    n_part = int(pk.max()) + 1 if len(pk) else 0
    keep_part = np.zeros(n_part, dtype=bool)
    keep_part[pk[part_filter(brand, container)]] = True
    if dtype == np.int64:
        # float64 weights hold these sums exactly (a part's quantities sum to a few thousand)
        s = np.bincount(lpk, weights=qty, minlength=n_part).astype(np.int64)
        n = np.bincount(lpk, minlength=n_part)
        small = 5 * qty * n[lpk] < s[lpk]
        hit = small & keep_part[lpk]
        if not hit.any():
            return {}
        return {"avg_yearly": int(price[hit].sum()) * 10 ** (SCALE - 1) // 70}
    # the control: the same query in the precision below (float32 throughout)
    s = np.bincount(lpk, weights=qty.astype(dtype), minlength=n_part).astype(dtype)
    n = np.bincount(lpk, minlength=n_part).astype(dtype)
    with np.errstate(divide="ignore", invalid="ignore"):
        small = qty.astype(dtype) < dtype(0.2) * (s / n)[lpk]
    hit = small & keep_part[lpk]
    if not hit.any():
        return {}
    total = price[hit].astype(dtype).sum(dtype=dtype) / dtype(100.0) / dtype(7.0)
    return {"avg_yearly": int(Decimal(float(total)).scaleb(SCALE).to_integral_value())}


def _parse_q17(rows) -> dict:
    rows = list(rows)
    if len(rows) != 1:
        raise ValueError(f"Q17 serves one row, got {len(rows)}")
    (value,) = rows[0]
    if value is None or value in ("", "NULL", "\\N"):
        return {}
    scaled = Decimal(str(value)).scaleb(SCALE)
    if scaled != scaled.to_integral_value():
        raise ValueError(f"{value!r} has more than {SCALE} fractional digits")
    return {"avg_yearly": int(scaled)}


# view name (as the configuration and workload files give it) -> (reference, parser of served rows)
VIEWS = {
    "q17": (q17, _parse_q17),
}


def differ(got, want) -> int:
    """How many answers differ between what was served and the reference:
    the one value missing, extra or another."""
    keys = set(got) | set(want)
    return sum(1 for k in keys if got.get(k) != want.get(k))
