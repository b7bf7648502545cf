"""Plain NumPy reference for the TPC-H view the benchmark serves (Q3).

Imports nothing of the program. Input is `Generator.live()` of
`chipbench/traffic/tpch.py` (host columns, i64); output is
{group tuple: exact integer}, money at scale 4 (cents x percent), the same
form `parse` gives for rows that came over pgwire (text), HTTP (JSON) or the
SUBSCRIBE stream. Dates are day numbers since 1992-01-01.
"""

from __future__ import annotations

from decimal import Decimal

import numpy as np


def _day(y: int, m: int, d: int) -> int:
    return int((np.datetime64(f"{y:04d}-{m:02d}-{d:02d}") - np.datetime64("1992-01-01")).astype(int))


Q3_DATE = _day(1995, 3, 15)


def _q3_joined(live: dict):
    """Lineitems of Q3 with their order's date and priority, before grouping."""
    ck, seg, _ = live["customer"]
    ok, ock, od, sp = live["orders"]
    lk, ep, dc, sd = live["lineitem"][:4]
    building = ck[seg == live["segments"].index("BUILDING")]
    o_keep = (od < Q3_DATE) & np.isin(ock, building)
    ok, od, sp = ok[o_keep], od[o_keep], sp[o_keep]
    order = np.argsort(ok, kind="stable")
    ok, od, sp = ok[order], od[order], sp[order]
    l_keep = sd > Q3_DATE
    lk, ep, dc = lk[l_keep], ep[l_keep], dc[l_keep]
    pos = np.searchsorted(ok, lk)
    hit = (pos < len(ok)) & (ok[np.minimum(pos, len(ok) - 1)] == lk) if len(ok) else np.zeros(len(lk), bool)
    pos = pos[hit]
    return lk[hit], ep[hit], dc[hit], od[pos], sp[pos]


def q3(live: dict, dtype=np.int64) -> dict:
    """TPC-H Q3 without ORDER BY / LIMIT: {(l_orderkey, o_orderdate,
    o_shippriority): sum(l_extendedprice * (1 - l_discount)) at scale 4}.
    `dtype` is the arithmetic's type: int64 is exact, the control passes float32."""
    lk, ep, dc, od, sp = _q3_joined(live)
    rev = ep.astype(dtype) * (100 - dc).astype(dtype)
    uniq, inv = np.unique(lk, return_inverse=True)
    sums = np.zeros(len(uniq), dtype=dtype)
    np.add.at(sums, inv, rev)
    first = np.zeros(len(uniq), dtype=np.int64)
    first[inv] = np.arange(len(lk))
    return {
        (int(k), int(od[i]), int(sp[i])): int(s)
        for k, i, s in zip(uniq.tolist(), first.tolist(), sums.tolist())
        if s != 0
    }


def _scale4(text) -> int:
    scaled = Decimal(str(text)) * 10_000
    if scaled != scaled.to_integral_value():
        raise ValueError(f"{text!r} is not a scale-4 number")
    return int(scaled)


def _parse_q3(rows) -> dict:
    out = {}
    for lk, rev, od, sp in rows:
        k = (int(lk), int(od), int(sp))
        if k in out:
            raise ValueError(f"group {k} twice")
        out[k] = _scale4(rev)
    return out


# view name (as the configuration and workload files give it) -> (reference, parser of served rows)
VIEWS = {
    "q3": (q3, _parse_q3),
}


def differ(got, want) -> int:
    """How many answers differ between what was served and the reference:
    groups missing, extra or with another value."""
    keys = set(got) | set(want)
    return sum(1 for k in keys if got.get(k) != want.get(k))
