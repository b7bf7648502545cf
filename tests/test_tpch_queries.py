"""More TPC-H-shaped queries through SQL, maintained incrementally vs oracles."""

import numpy as np
import pytest
import tpch_q3

from materialize_tpu.adapter import Coordinator


@pytest.fixture
def coord():
    c = Coordinator()
    c.execute(tpch_q3.SOURCE_SQL)
    return c


def li_state(c):
    """(orderkey, price in cents, discount in percent, shipdate, quantity, partkey) of the live lineitems."""
    li = c.generators[0][0].live()["lineitem"]
    return tuple(li[k] for k in ("l_orderkey", "l_extendedprice", "l_discount", "l_shipdate", "l_quantity", "l_partkey"))


def test_q6_forecast_revenue(coord):
    """Q6: sum(extendedprice * discount) under range filters."""
    coord.execute(
        """CREATE MATERIALIZED VIEW q6 AS
           SELECT sum(l_extendedprice * l_discount) AS revenue
           FROM lineitem
           WHERE l_shipdate >= DATE '1994-01-01' AND l_shipdate < DATE '1995-01-01'
             AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24"""
    )
    for t in range(2):
        coord.advance()

    def oracle():
        lk, ep, dc, sd, qty, pk = (np.asarray(c) for c in li_state(coord))
        from materialize_tpu.storage.generator import date_num

        lo, hi = date_num(1994, 1, 1), date_num(1995, 1, 1)
        m = (sd >= lo) & (sd < hi) & (dc >= 5) & (dc <= 7) & (qty < 24)
        return int((ep[m] * dc[m]).sum())

    rows = coord.execute("SELECT * FROM q6").rows
    got = round(rows[0][0] * 10_000) if rows else 0
    assert got == oracle()


def test_q1_shaped_aggregation(coord):
    """Q1-shaped: multi-aggregate GROUP BY with avg over the fact table."""
    coord.execute(
        """CREATE MATERIALIZED VIEW q1 AS
           SELECT l_partkey % 3 AS grp, sum(l_quantity) AS sum_qty,
                  sum(l_extendedprice) AS sum_price, avg(l_quantity) AS avg_qty,
                  count(*) AS n
           FROM lineitem WHERE l_shipdate <= DATE '1998-09-02'
           GROUP BY l_partkey % 3"""
    )
    for t in range(2):
        coord.advance()
    lk, ep, dc, sd, qty, pk = (np.asarray(c) for c in li_state(coord))
    from materialize_tpu.storage.generator import date_num

    cutoff = date_num(1998, 9, 2)
    m = sd <= cutoff
    want = {}
    for g in (0, 1, 2):
        gm = m & (pk % 3 == g)
        if gm.any():
            want[g] = (
                int(qty[gm].sum()),
                int(ep[gm].sum()),
                qty[gm].mean(),
                int(gm.sum()),
            )
    rows = coord.execute("SELECT * FROM q1 ORDER BY grp").rows
    got = {r[0]: r[1:] for r in rows}
    assert set(got) == set(want)
    for g in want:
        sq, sp, aq, n = want[g]
        assert got[g][0] == sq
        assert round(got[g][1] * 100) == sp
        assert abs(got[g][2] - aq) < 1e-2
        assert got[g][3] == n


def test_q18_shape_having(coord):
    """Q18-shaped: join + GROUP BY + HAVING sum threshold."""
    coord.execute(
        """CREATE MATERIALIZED VIEW big_orders AS
           SELECT o_orderkey, o_custkey, sum(l_quantity) AS total_qty
           FROM orders, lineitem
           WHERE o_orderkey = l_orderkey
           GROUP BY o_orderkey, o_custkey
           HAVING sum(l_quantity) > 150"""
    )
    coord.advance()
    lk, ep, dc, sd, qty, pk = (np.asarray(c) for c in li_state(coord))
    gen = coord.generators[0][0]
    orders = gen.live()["orders"]
    ok, ock = orders["o_orderkey"], orders["o_custkey"]
    cust_of = dict(zip(ok.tolist(), ock.tolist()))
    sums: dict = {}
    for k, q in zip(lk.tolist(), qty.tolist()):
        sums[k] = sums.get(k, 0) + q
    want = sorted(
        (k, cust_of[k], s) for k, s in sums.items() if s > 150 and k in cust_of
    )
    got = sorted(coord.execute("SELECT * FROM big_orders").rows)
    assert got == want
