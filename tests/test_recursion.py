"""WITH MUTUALLY RECURSIVE + plain CTEs: fixpoint dataflows through SQL.

The transitive-closure / reachability workloads that exercise the reference's
iterative scopes (render.rs:887, PointStamp product timestamps).
"""

import pytest

from materialize_tpu.adapter import Coordinator


@pytest.fixture
def coord():
    return Coordinator()


def test_plain_cte(coord):
    coord.execute("CREATE TABLE t (a int)")
    coord.execute("INSERT INTO t VALUES (1), (2), (3)")
    r = coord.execute(
        "WITH big AS (SELECT a FROM t WHERE a > 1) SELECT count(*) FROM big"
    )
    assert r.rows == [(2,)]


def test_transitive_closure(coord):
    coord.execute("CREATE TABLE edges (src int, dst int)")
    coord.execute("INSERT INTO edges VALUES (1, 2), (2, 3), (3, 4)")
    r = coord.execute(
        """WITH MUTUALLY RECURSIVE
             reach (src int, dst int) AS (
               SELECT src, dst FROM edges
               UNION
               SELECT r.src, e.dst FROM reach r, edges e WHERE r.dst = e.src
             )
           SELECT src, dst FROM reach ORDER BY src, dst"""
    )
    assert r.rows == [
        (1, 2), (1, 3), (1, 4),
        (2, 3), (2, 4),
        (3, 4),
    ]


def test_recursive_materialized_view_incremental(coord):
    coord.execute("CREATE TABLE edges (src int, dst int)")
    coord.execute("INSERT INTO edges VALUES (1, 2), (2, 3)")
    coord.execute(
        """CREATE MATERIALIZED VIEW reach_mv AS
           WITH MUTUALLY RECURSIVE
             reach (src int, dst int) AS (
               SELECT src, dst FROM edges
               UNION
               SELECT r.src, e.dst FROM reach r, edges e WHERE r.dst = e.src
             )
           SELECT src, dst FROM reach"""
    )
    assert coord.execute("SELECT * FROM reach_mv ORDER BY src, dst").rows == [
        (1, 2), (1, 3), (2, 3),
    ]
    # add an edge: closure extends incrementally
    coord.execute("INSERT INTO edges VALUES (3, 4)")
    assert coord.execute("SELECT * FROM reach_mv ORDER BY src, dst").rows == [
        (1, 2), (1, 3), (1, 4), (2, 3), (2, 4), (3, 4),
    ]
    # remove the middle edge: everything through it retracts
    coord.execute("DELETE FROM edges WHERE src = 2")
    assert coord.execute("SELECT * FROM reach_mv ORDER BY src, dst").rows == [
        (1, 2), (3, 4),
    ]


def test_mutual_recursion_two_bindings(coord):
    coord.execute("CREATE TABLE seed (n int)")
    coord.execute("INSERT INTO seed VALUES (10)")
    # evens/odds countdown: evens(n) -> odds(n-1) -> evens(n-2) …
    r = coord.execute(
        """WITH MUTUALLY RECURSIVE
             evens (n int) AS (
               SELECT n FROM seed
               UNION SELECT n - 1 FROM odds WHERE n > 0
             ),
             odds (n int) AS (
               SELECT n - 1 FROM evens WHERE n > 0
             )
           SELECT n FROM evens ORDER BY n"""
    )
    assert r.rows == [(0,), (2,), (4,), (6,), (8,), (10,)]


def test_nonconvergent_raises(coord, programs_built):
    coord.execute("CREATE TABLE s (n int)")
    coord.execute("INSERT INTO s VALUES (1)")
    before = programs_built()
    with pytest.raises(RuntimeError, match="converge"):
        coord.execute(
            """WITH MUTUALLY RECURSIVE
                 grow (n int) AS (
                   SELECT n FROM s UNION SELECT n + 1 FROM grow
                 )
               SELECT count(*) FROM grow"""
        )
    # 100 iterations over a relation that grows to 100 rows: operator state
    # moves through pow2 buckets, so programs grow with log(rows) (249 in a
    # fresh process); with state sized by the iteration count it was ~950
    assert programs_built() - before < 400


_CLOSURE = """WITH MUTUALLY RECURSIVE
     reach (src int, dst int) AS (
       SELECT src, dst FROM edges
       UNION
       SELECT r.src, e.dst FROM reach r, edges e WHERE r.dst = e.src
     )
   SELECT src, dst FROM reach ORDER BY src, dst"""


def _closure_of(edges):
    c = Coordinator()
    c.execute("CREATE TABLE edges (src int, dst int)")
    c.execute("INSERT INTO edges VALUES " + ", ".join(map(str, edges)))
    return c.execute(_CLOSURE).rows


def test_second_run_over_changed_data_compiles_nothing(programs_built):
    """Shapes follow capacity buckets, not the data: other values and another
    number of iterations (3, then 2) reuse every program of the first run."""
    assert len(_closure_of([(1, 2), (2, 3), (3, 4)])) == 6
    before = programs_built()
    assert _closure_of([(7, 5), (9, 7), (20, 21)]) == [
        (7, 5), (9, 5), (9, 7), (20, 21),
    ]
    assert programs_built() == before
