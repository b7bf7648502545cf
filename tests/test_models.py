"""Canonical workloads end-to-end: auction configs + TPC-H Q3 vs oracles."""

import numpy as np

from materialize_tpu.dataflow import Dataflow
from materialize_tpu.models import auction, tpch
from materialize_tpu.storage import AuctionGenerator, TpchGenerator


def test_auction_sum_count_and_topk():
    gen = AuctionGenerator(seed=3)
    df_sum = Dataflow(auction.bids_sum_count())
    df_top = Dataflow(auction.max_bid_per_auction())
    all_bids = []
    for tick in range(4):
        batches = gen.next_tick(tick, 50)
        df_sum.step(tick, {"bids": batches["bids"]})
        df_top.step(tick, {"bids": batches["bids"]})
        for row in batches["bids"].to_rows():
            all_bids.append(row[0])
    # oracle
    want_sum = {}
    best = {}
    for (bid, buyer, auc, amt, bt) in all_bids:
        s, c = want_sum.get(auc, (0, 0))
        want_sum[auc] = (s + amt, c + 1)
        cur = best.get(auc)
        row = (bid, buyer, auc, amt, bt)
        if cur is None or amt > cur[3]:
            best[auc] = row
    got_sum = df_sum.peek("idx_bids_sum")
    assert got_sum == sorted((a, s, c) for a, (s, c) in want_sum.items())
    got_top = df_top.peek("idx_topk")
    assert {r[2]: r for r in got_top} == {r[2]: r for r in best.values()} or len(
        got_top
    ) == len(best)
    # amounts must match exactly (row identity can differ only on ties)
    assert sorted(r[3] for r in got_top) == sorted(r[3] for r in best.values())


def test_auction_join():
    gen = AuctionGenerator(seed=4)
    df = Dataflow(auction.auctions_join_bids())
    auctions, bids = [], []
    for tick in range(3):
        b = gen.next_tick(tick, 30)
        df.step(tick, {"auctions": b["auctions"], "bids": b["bids"]})
        auctions += [r[0] for r in b["auctions"].to_rows()]
        bids += [r[0] for r in b["bids"].to_rows()]
    want = []
    amap = {a[0]: a for a in auctions}
    for b in bids:
        a = amap.get(b[2])
        if a is not None:
            want.append(a + b)
    assert df.peek("idx_join") == sorted(want)


def test_tpch_q3_through_sql():
    """Q3 as SQL text over the TPC-H source: planner picks the delta join and
    the maintained MV matches the brute-force oracle after refreshes."""
    import tpch_q3
    from materialize_tpu.adapter import Coordinator

    c = Coordinator()
    c.execute(tpch_q3.SOURCE_SQL)
    c.execute(tpch_q3.VIEW_SQL)
    for _ in range(3):
        c.advance()
    rows = c.execute("SELECT * FROM q3").rows
    gen = c.generators[0][0]
    seg_code = c.catalog.dict.lookup("BUILDING")
    assert seg_code is not None  # resolved via the shared catalog dictionary
    want = tpch.q3_oracle(
        *tpch.q3_inputs(gen.live()),
        building_code=seg_code,
    )
    got = {}
    for (lk, rev, od, sp) in rows:
        got[(lk, od, sp)] = round(rev * 10_000)  # NUMERIC scale-4 decode
    want = {k: v for k, v in want.items() if v != 0}
    assert got == want


def test_tpch_q3_incremental_vs_oracle():
    gen = TpchGenerator(sf=0.001, seed=7, columns=tpch.Q3_COLUMNS)
    df = Dataflow(tpch.q3())
    init = gen.initial_batches(0)
    df.step(0, {k: init[k] for k in ("customer", "orders", "lineitem")})
    # several RF1/RF2 refresh ticks
    for tick in range(1, 5):
        df.step(tick, gen.refresh(tick, frac=0.01))
    got = {}
    for row in df.peek("idx_q3"):
        got[(row[0], row[1], row[2])] = row[3]
    want = tpch.q3_oracle(*tpch.q3_inputs(gen.live()))
    want = {k: v for k, v in want.items() if v != 0}
    assert got == want
