"""Drive materialize_tpu end-to-end at its package boundary, on real TPU.

Scenario: a stream of auction bids arrives in ticks; we incrementally maintain
  (1) SUM(amount), COUNT(*) per auction            (accumulable reduce)
  (2) bids joined with auctions on auction_id       (linear join, 3-term form)
  (3) top-1 bid per auction                         (topk kernel)
and cross-check the integrated outputs against a brute-force recompute.
"""
import numpy as np
import jax

import materialize_tpu  # noqa: F401  (enables x64)
from materialize_tpu.arrangement import Arrangement, arrange_batch
from materialize_tpu.expr import Column, Literal
from materialize_tpu.ops import consolidate
from materialize_tpu.ops.join import join_against
from materialize_tpu.ops.reduce import AccumState, AggregateExpr, accumulable_step
from materialize_tpu.ops.topk import TopKPlan, topk_step
from materialize_tpu.repr import UpdateBatch, bucket_cap

print("devices:", jax.devices())

rng = np.random.default_rng(42)

# auctions: (id, seller) static-ish; bids: (id, auction_id, amount) streaming
n_auctions = 20
auc_id = np.arange(n_auctions, dtype=np.int64)
auc_seller = rng.integers(100, 110, n_auctions).astype(np.int64)

A_arr = Arrangement(key_cols=(0,))
B_arr = Arrangement(key_cols=(1,))  # bids keyed by auction_id
topk_arr = Arrangement(key_cols=(1,))
sumcount_state = AccumState.empty(
    8, (np.dtype(np.int64),), (np.dtype(np.int64), np.dtype(np.int64))
)
AGGS = (AggregateExpr("sum", Column(2)), AggregateExpr("count", Literal(1)))
plan = TopKPlan(group_cols=(1,), order_by=((2, True),), limit=1)

dA0 = arrange_batch(
    UpdateBatch.build((), (auc_id, auc_seller), [0] * n_auctions, [1] * n_auctions), (0,)
)
A_arr.insert(dA0, already_keyed=True)

sum_out, join_out, topk_out = {}, {}, {}
all_bids = {}
bid_id = 0
for tick in range(1, 8):
    n = int(rng.integers(5, 40))
    ids = np.arange(bid_id, bid_id + n, dtype=np.int64)
    bid_id += n
    aucs = rng.integers(0, n_auctions, n).astype(np.int64)
    amts = rng.integers(1, 1000, n).astype(np.int64)
    diffs = [1] * n
    # occasionally retract an old bid
    retract = [b for b in list(all_bids) if rng.random() < 0.05][:5]
    for b in retract:
        ids = np.append(ids, b[0]); aucs = np.append(aucs, b[1]); amts = np.append(amts, b[2])
        diffs.append(-1)
        del all_bids[b]
    for i in range(n):
        all_bids[(int(ids[i]), int(aucs[i]), int(amts[i]))] = 1

    delta = UpdateBatch.build((), (ids, aucs, amts), [tick] * len(diffs), diffs)

    # (1) reduce
    sumcount_state, out, _errs, _counts = accumulable_step(sumcount_state, delta, (1,), AGGS, tick)
    sumcount_state = sumcount_state.with_capacity(bucket_cap(int(sumcount_state.count())))
    for d, _t, df in out.to_rows():
        sum_out[d] = sum_out.get(d, 0) + df

    # (2) join dBids ⋈ Auctions (auctions static this run)
    dB = arrange_batch(delta, (1,))
    for ob in join_against(dB, A_arr.batches):
        for d, _t, df in ob.to_rows():
            join_out[d] = join_out.get(d, 0) + df
    B_arr.insert(dB, already_keyed=True)

    # (3) top-1 per auction
    dT = arrange_batch(delta, (1,))
    out = topk_step(topk_arr, dT, plan, tick)
    for d, _t, df in out.to_rows():
        topk_out[d] = topk_out.get(d, 0) + df

# ---- oracle checks ----
sum_out = {k: v for k, v in sum_out.items() if v != 0}
join_out = {k: v for k, v in join_out.items() if v != 0}
topk_out = {k: v for k, v in topk_out.items() if v != 0}

want_sum = {}
for (bid, auc, amt) in all_bids:
    s, c = want_sum.get(auc, (0, 0))
    want_sum[auc] = (s + amt, c + 1)
assert sum_out == {(a, s, c): 1 for a, (s, c) in want_sum.items()}, "SUM/COUNT mismatch"

want_join = {}
for (bid, auc, amt) in all_bids:
    want_join[(bid, auc, amt, auc, int(auc_seller[auc]))] = 1
assert join_out == want_join, "JOIN mismatch"

# tie-break: engine uses remaining cols ascending; mimic: highest amt, then smallest id
best2 = {}
for (bid, auc, amt) in sorted(all_bids, key=lambda r: (r[1], -r[2], r[0])):
    if auc not in best2:
        best2[auc] = (bid, auc, amt)
want_top2 = {v: 1 for v in best2.values()}
assert topk_out == want_top2, f"TOPK mismatch: {topk_out} != {want_top2}"

print("bids live:", len(all_bids), "| groups:", len(want_sum))
print("SUM/COUNT OK | JOIN OK | TOP1 OK — all maintained incrementally over 7 ticks")
