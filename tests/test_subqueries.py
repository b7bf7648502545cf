"""Uncorrelated subqueries: IN (SELECT …), EXISTS, scalar subqueries."""

import pytest

from materialize_tpu.adapter import Coordinator
from materialize_tpu.sql.plan import PlanError


@pytest.fixture
def coord():
    c = Coordinator()
    c.execute("CREATE TABLE t (a int, b int)")
    c.execute("CREATE TABLE u (x int)")
    c.execute("INSERT INTO t VALUES (1, 10), (2, 20), (3, 30)")
    c.execute("INSERT INTO u VALUES (1), (3), (3)")
    return c


def test_in_subquery_semijoin(coord):
    r = coord.execute("SELECT a, b FROM t WHERE a IN (SELECT x FROM u) ORDER BY a")
    # duplicate 3 in u must not duplicate t's row (semijoin, not join)
    assert r.rows == [(1, 10), (3, 30)]


def test_exists(coord):
    assert coord.execute(
        "SELECT count(*) FROM t WHERE EXISTS (SELECT x FROM u WHERE x > 2)"
    ).rows == [(3,)]
    assert coord.execute(
        "SELECT count(*) FROM t WHERE EXISTS (SELECT x FROM u WHERE x > 99)"
    ).rows == [(0,)]  # global aggregate over empty input: one default row


def test_scalar_subquery(coord):
    r = coord.execute("SELECT a, b - (SELECT min(x) FROM u) FROM t ORDER BY a")
    assert r.rows == [(1, 9), (2, 19), (3, 29)]
    r = coord.execute("SELECT a FROM t WHERE b > (SELECT sum(x) FROM u) ORDER BY a")
    # sum(x) = 7 -> b in {10, 20, 30} all qualify
    assert r.rows == [(1,), (2,), (3,)]


def test_in_subquery_maintained_in_mv(coord):
    coord.execute(
        "CREATE MATERIALIZED VIEW m AS SELECT a FROM t WHERE a IN (SELECT x FROM u)"
    )
    assert coord.execute("SELECT * FROM m ORDER BY a").rows == [(1,), (3,)]
    coord.execute("INSERT INTO u VALUES (2)")
    assert coord.execute("SELECT * FROM m ORDER BY a").rows == [(1,), (2,), (3,)]
    coord.execute("DELETE FROM u WHERE x = 3")
    assert coord.execute("SELECT * FROM m ORDER BY a").rows == [(1,), (2,)]


def test_not_in_direct(coord):
    r = coord.execute("SELECT a FROM t WHERE a NOT IN (SELECT x FROM u) ORDER BY a")
    assert r.rows == [(2,)]


def test_stddev_variance(coord):
    import math

    coord.execute("CREATE TABLE v (g int, x int)")
    coord.execute("INSERT INTO v VALUES (1, 2), (1, 4), (1, 6), (2, 5)")
    r = coord.execute(
        "SELECT g, var_pop(x), stddev_pop(x), variance(x) FROM v GROUP BY g ORDER BY g"
    )
    (g1, vp1, sp1, vs1), (g2, vp2, sp2, vs2) = r.rows
    assert g1 == 1 and abs(vp1 - 8 / 3) < 1e-3
    assert abs(sp1 - math.sqrt(8 / 3)) < 1e-3
    assert abs(vs1 - 4.0) < 1e-3  # sample variance of {2,4,6}
    assert g2 == 2 and vp2 == 0.0 and vs2 == 0.0  # n=1: samp clamps to 0


def test_not_in_antijoin(coord):
    r = coord.execute("SELECT a FROM t WHERE a NOT IN (SELECT x FROM u) ORDER BY a")
    assert r.rows == [(2,)]
    # maintained incrementally
    coord.execute(
        "CREATE MATERIALIZED VIEW anti AS SELECT a FROM t WHERE a NOT IN (SELECT x FROM u)"
    )
    assert coord.execute("SELECT * FROM anti").rows == [(2,)]
    coord.execute("INSERT INTO u VALUES (2)")
    assert coord.execute("SELECT * FROM anti").rows == []
    coord.execute("DELETE FROM u WHERE x = 2")
    assert coord.execute("SELECT * FROM anti").rows == [(2,)]


def test_not_exists(coord):
    assert coord.execute(
        "SELECT count(*) FROM t WHERE NOT EXISTS (SELECT x FROM u WHERE x > 99)"
    ).rows == [(3,)]
    assert coord.execute(
        "SELECT count(*) FROM t WHERE NOT EXISTS (SELECT x FROM u)"
    ).rows == [(0,)]


def test_correlated_scalar_subquery_decorrelation(coord):
    """WHERE v < (SELECT avg over rows with matching key) — the Q17 shape."""
    coord.execute("CREATE TABLE li (pk int, qty int)")
    coord.execute(
        "INSERT INTO li VALUES (1, 2), (1, 10), (1, 30), (2, 5), (2, 7)"
    )
    r = coord.execute(
        """SELECT pk, qty FROM li l
           WHERE qty < (SELECT avg(l2.qty) FROM li l2 WHERE l2.pk = l.pk)
           ORDER BY pk, qty"""
    )
    # group 1 avg = 14 -> {2, 10}; group 2 avg = 6 -> {5}
    assert r.rows == [(1, 2), (1, 10), (2, 5)]
    # maintained incrementally
    coord.execute(
        """CREATE MATERIALIZED VIEW below_avg AS
           SELECT pk, qty FROM li l
           WHERE qty < (SELECT avg(l2.qty) FROM li l2 WHERE l2.pk = l.pk)"""
    )
    coord.execute("INSERT INTO li VALUES (1, 1000)")  # avg(1) jumps to 260.5
    r = coord.execute("SELECT * FROM below_avg ORDER BY pk, qty")
    assert r.rows == [(1, 2), (1, 10), (1, 30), (2, 5)]


@pytest.mark.parametrize(
    "predicate",
    [
        "l.qty * 5 < (SELECT avg(l2.qty) FROM l l2 WHERE l2.pk = l.pk)",
        # the published form (until PR 30 `0.2 * avg` was `2 * avg`)
        "l.qty < (SELECT 0.2 * avg(l2.qty) FROM l l2 WHERE l2.pk = l.pk)",
    ],
    ids=["qty_times_5", "published"],
)
def test_correlated_q17_shape(coord, predicate):
    """0.2 * avg correlated threshold with an outer join filter."""
    coord.execute("CREATE TABLE l (pk int, price int, qty int)")
    coord.execute("CREATE TABLE p (pk int, brand int)")
    coord.execute(
        "INSERT INTO l VALUES (1, 100, 1), (1, 200, 50), (2, 300, 2), (2, 50, 40)"
    )
    coord.execute("INSERT INTO p VALUES (1, 7), (2, 8)")
    r = coord.execute(
        f"""SELECT sum(l.price) FROM l, p
           WHERE p.pk = l.pk AND p.brand = 7 AND {predicate}"""
    )
    # group 1 avg qty = 25.5; rows with qty*5 < 25.5: qty=1 -> price 100
    assert r.rows == [(100,)]


def test_not_in_outside_where_conjunct_rejected(coord):
    """NOT IN under OR or in the select list must error, not misplan."""
    with pytest.raises(PlanError, match="top-level"):
        coord.execute(
            "SELECT a FROM t WHERE a NOT IN (SELECT x FROM u) OR a = 1"
        )
    with pytest.raises(PlanError, match="top-level"):
        coord.execute("SELECT a, a NOT IN (SELECT x FROM u) FROM t")
    # AND-connected top-level conjuncts still work
    r = coord.execute(
        "SELECT a FROM t WHERE a NOT IN (SELECT x FROM u) AND a > 0 ORDER BY a"
    )
    assert r.rows == [(2,)]
