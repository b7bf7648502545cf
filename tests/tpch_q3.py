"""The served TPC-H Q3 that the SQL tests share.

One generator scale and one view text: every test that hydrates it asks for
the same programs, and the run's compile cache (conftest.py) builds each once.
"""

SOURCE_SQL = "CREATE SOURCE tp FROM LOAD GENERATOR TPCH (SCALE FACTOR 0.001)"

# A scale whose refreshes stay inside one pow2 bucket (22 orders in, 22 out:
# 44 order rows under 64, about 176 lineitem rows under 256), as SF1's do
# (3,000 under 4,096; 12,000 under 16,384). At 0.001 a refresh is 2 to 14
# lineitem rows, across the 8 | 16 boundary, so shapes follow the draw.
SOURCE_SQL_STEADY = "CREATE SOURCE tp FROM LOAD GENERATOR TPCH (SCALE FACTOR 0.015)"

VIEW_SQL = """CREATE MATERIALIZED VIEW q3 AS
   SELECT l_orderkey, sum(l_extendedprice * (1 - l_discount)) AS revenue,
          o_orderdate, o_shippriority
   FROM customer, orders, lineitem
   WHERE c_mktsegment = 'BUILDING' AND c_custkey = o_custkey
     AND l_orderkey = o_orderkey AND o_orderdate < DATE '1995-03-15'
     AND l_shipdate > DATE '1995-03-15'
   GROUP BY l_orderkey, o_orderdate, o_shippriority"""
