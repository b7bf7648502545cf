"""Plain NumPy reference for TPC-H Q18 as the benchmark serves it.

Imports nothing of the program. Input is `Generator.live()` of
`chipbench/traffic/tpch_full.py` (table -> column name -> host values, i64;
`o_totalprice` in cents, `o_orderdate` a day number since 1992-01-01);
output is `{row: 1}` over the rows the view holds, each row
`(c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice in cents,
sum(l_quantity))` with `c_name` the string that is served. `parse` gives the
same form for rows that came over pgwire (text), HTTP (JSON) or the SUBSCRIBE
stream.

    SELECT c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice, sum(l_quantity)
    FROM customer, orders, lineitem
    WHERE o_orderkey IN (SELECT l_orderkey FROM lineitem GROUP BY l_orderkey
                         HAVING sum(l_quantity) > 300)
      AND c_custkey = o_custkey AND o_orderkey = l_orderkey
    GROUP BY c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
    ORDER BY o_totalprice DESC, o_orderdate LIMIT 100

Every group is one order (its key is in the GROUP BY), so `sum(l_quantity)`
is the order's own sum, the one the subquery compared with the threshold;
both are exact integers. `c_name` is TPC-H's rule, `Customer#` and the row's
key in nine digits.

**The tie rule past the two ORDER BY keys** is the one the view's TopK
applies (`materialize_tpu/ops/topk.py::_topk_select`): the rows of the group
are sorted by the ORDER BY columns, then by every other column of the row
ascending, the first column most significant. The program holds a string as
its dictionary code, and the generator interns the customers' names in key
order, so `c_name` ascending there is `c_custkey` ascending here: ties go by
`c_custkey`, then `o_orderkey`, then the sum.
"""

from __future__ import annotations

from decimal import Decimal

import numpy as np

QUANTITY = 300  # the specification's validation value (section 2.4.18.3)
LIMIT = 100
PRICE_SCALE = 2  # o_totalprice is NUMERIC at scale 2: cents


def customer_name(custkey: int) -> str:
    return f"Customer#{int(custkey):09d}"


def q18(live: dict, dtype=np.int64, quantity: int = QUANTITY, limit: int = LIMIT) -> dict:
    """`{(c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice, sum): 1}`
    over the rows of the view. `dtype` is the arithmetic's type: int64 is
    exact, the control passes float32. `quantity` and `limit` stand for the
    published threshold and row count (a test passes others)."""
    li, orders = live["lineitem"], live["orders"]
    keys, inv = np.unique(li["l_orderkey"], return_inverse=True)
    sums = np.zeros(len(keys), dtype=dtype)
    np.add.at(sums, inv, li["l_quantity"].astype(dtype))
    big = sums > quantity
    okey = orders["o_orderkey"]
    pos = np.searchsorted(keys, okey)
    found = (pos < len(keys)) & (keys[np.minimum(pos, len(keys) - 1)] == okey)
    kept = np.flatnonzero(found)
    kept = kept[big[pos[kept]]]
    custkey, date = orders["o_custkey"][kept], orders["o_orderdate"][kept]
    price, total = orders["o_totalprice"][kept].astype(dtype), sums[pos[kept]]
    # every order's customer exists: c_custkey counts from 0 and is the row's position
    assert np.array_equal(live["customer"]["c_custkey"][custkey], custkey)
    order = np.lexsort((total, okey[kept], custkey, date, -price))[:limit]
    return {
        (customer_name(c), int(c), int(k), int(d), int(p), int(t)): 1
        for c, k, d, p, t in zip(custkey[order].tolist(), okey[kept][order].tolist(), date[order].tolist(),
                                 price[order].tolist(), total[order].tolist())
    }


def _cents(value) -> int:
    scaled = Decimal(str(value)).scaleb(PRICE_SCALE)
    if scaled != scaled.to_integral_value():
        raise ValueError(f"{value!r} has more than {PRICE_SCALE} fractional digits")
    return int(scaled)


def _parse_q18(rows) -> dict:
    out: dict = {}
    for name, custkey, orderkey, orderdate, totalprice, total in rows:
        row = (str(name), int(custkey), int(orderkey), int(orderdate), _cents(totalprice), int(total))
        if row in out:
            raise ValueError(f"row {row} twice")
        out[row] = 1
    return out


# view name (as the configuration and workload files give it) -> (reference, parser of served rows)
VIEWS = {
    "q18": (q18, _parse_q18),
}


def differ(got, want) -> int:
    """How many rows differ between what was served and the reference:
    missing or extra."""
    return len(set(got) ^ set(want))
