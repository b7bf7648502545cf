"""`LOAD GENERATOR TPCH` in the specification's schema: TPC-H's eight tables
with every column, the derived columns following dbgen's rules, and views over
columns and tables beyond Q3's, maintained through refreshes and compared with
oracles over the generator's live rows."""

import numpy as np
import pytest

from materialize_tpu.adapter import Coordinator
from materialize_tpu.storage.generator import TPCH_TABLES, TpchGenerator, date_num

SOURCE_SQL = "CREATE SOURCE tp FROM LOAD GENERATOR TPCH (SCALE FACTOR 0.001)"

# TPC-H v3 section 1.4: each table's columns, in order
SPEC_COLUMNS = {
    "region": "r_regionkey r_name r_comment",
    "nation": "n_nationkey n_name n_regionkey n_comment",
    "supplier": "s_suppkey s_name s_address s_nationkey s_phone s_acctbal s_comment",
    "customer": "c_custkey c_name c_address c_nationkey c_phone c_acctbal c_mktsegment c_comment",
    "part": "p_partkey p_name p_mfgr p_brand p_type p_size p_container p_retailprice p_comment",
    "partsupp": "ps_partkey ps_suppkey ps_availqty ps_supplycost ps_comment",
    "orders": "o_orderkey o_custkey o_orderstatus o_totalprice o_orderdate o_orderpriority o_clerk "
              "o_shippriority o_comment",
    "lineitem": "l_orderkey l_partkey l_suppkey l_linenumber l_quantity l_extendedprice l_discount l_tax "
                "l_returnflag l_linestatus l_shipdate l_commitdate l_receiptdate l_shipinstruct l_shipmode "
                "l_comment",
}


@pytest.fixture(scope="module")
def served():
    c = Coordinator()
    c.execute(SOURCE_SQL)
    return c


def _decode(c, codes) -> list:
    return c.catalog.dict.decode_many(codes)


@pytest.mark.parametrize("table", list(SPEC_COLUMNS))
def test_every_table_has_the_specification_columns(served, table):
    assert TPCH_TABLES[table].names == tuple(SPEC_COLUMNS[table].split())
    assert served.catalog.get(table).desc.names == tuple(SPEC_COLUMNS[table].split())
    live = served.generators[0][0].live()[table]
    n = len(next(iter(live.values())))
    assert served.execute(f"SELECT count(*) FROM {table}").rows == [(n,)]


@pytest.fixture(scope="module")
def refreshed():
    gen = TpchGenerator(sf=0.002, seed=5)
    gen.initial()
    for _ in range(4):
        gen.refresh_rows(frac=0.01)
    return gen.live()


def test_orders_follow_their_lineitems(refreshed):
    """o_totalprice is the sum of price x (1 + tax) x (1 - discount) in cents,
    o_orderstatus F / O / P as all, none or some of its lines are shipped by
    CURRENTDATE, and each order's lines are numbered from 1."""
    o, li = refreshed["orders"], refreshed["lineitem"]
    assert np.array_equal(np.unique(li["l_orderkey"]), o["o_orderkey"])
    for row in range(0, len(o["o_orderkey"]), 37):
        lines = li["l_orderkey"] == o["o_orderkey"][row]
        charged = (li["l_extendedprice"][lines] * (100 + li["l_tax"][lines]) * (100 - li["l_discount"][lines])).sum()
        assert o["o_totalprice"][row] == (charged + 5_000) // 10_000
        assert sorted(li["l_linenumber"][lines]) == list(range(1, lines.sum() + 1))
        shipped = li["l_shipdate"][lines] <= date_num(1995, 6, 17)
        status = 0 if shipped.all() else (1 if not shipped.any() else 2)  # F, O, P before a dictionary
        assert o["o_orderstatus"][row] == status


def test_suppliers_and_dates_follow_dbgen(refreshed):
    li, ps = refreshed["lineitem"], refreshed["partsupp"]
    pairs = set(zip(ps["ps_partkey"].tolist(), ps["ps_suppkey"].tolist()))
    assert len(pairs) == len(ps["ps_partkey"]) == 4 * len(refreshed["part"]["p_partkey"])
    assert all(p in pairs for p in zip(li["l_partkey"].tolist(), li["l_suppkey"].tolist()))
    assert ((li["l_receiptdate"] - li["l_shipdate"] >= 1) & (li["l_receiptdate"] - li["l_shipdate"] <= 30)).all()
    returned = li["l_receiptdate"] <= date_num(1995, 6, 17)
    assert (li["l_returnflag"][~returned] == 2).all() and (li["l_returnflag"][returned] <= 1).all()


def test_flag_groups_over_lineitem_equal_the_oracle():
    """A Q1-shaped view grouped by the two derived flag columns, after refreshes
    that insert and retract whole rows of all 16 columns."""
    c = Coordinator()
    c.execute(SOURCE_SQL)
    c.execute(
        """CREATE MATERIALIZED VIEW flags AS
           SELECT l_returnflag, l_linestatus, sum(l_quantity) AS sum_qty, count(*) AS n
           FROM lineitem WHERE l_shipdate <= DATE '1998-12-01' - INTERVAL '90' DAY (3)
           GROUP BY l_returnflag, l_linestatus"""
    )
    for _ in range(3):
        c.advance()
    li = c.generators[0][0].live()["lineitem"]
    keep = li["l_shipdate"] <= date_num(1998, 12, 1) - 90
    want = {}
    for f, s, q in zip(_decode(c, li["l_returnflag"][keep]), _decode(c, li["l_linestatus"][keep]),
                       li["l_quantity"][keep].tolist()):
        qty, n = want.get((f, s), (0, 0))
        want[(f, s)] = (qty + q, n + 1)
    got = {(f, s): (q, n) for f, s, q, n in c.execute("SELECT * FROM flags").rows}
    assert got == want and len(want) >= 3


def test_suppliers_per_nation_of_a_region_equal_the_oracle(served):
    """A three-way join over supplier, nation and region, with a string filter."""
    served.execute(
        """CREATE MATERIALIZED VIEW asia AS
           SELECT n_name, count(*) AS n FROM supplier, nation, region
           WHERE s_nationkey = n_nationkey AND n_regionkey = r_regionkey AND r_name = 'ASIA'
           GROUP BY n_name"""
    )
    live = served.generators[0][0].live()
    names = _decode(served, live["nation"]["n_name"])
    regions = _decode(served, live["region"]["r_name"])
    want = {}
    for nk in live["supplier"]["s_nationkey"].tolist():
        if regions[live["nation"]["n_regionkey"][nk]] == "ASIA":
            want[names[nk]] = want.get(names[nk], 0) + 1
    assert dict(served.execute("SELECT * FROM asia").rows) == want
