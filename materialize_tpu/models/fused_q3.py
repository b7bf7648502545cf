"""TPC-H Q3 as ONE fused XLA program per tick — single-chip or mesh-sharded.

This is the flagship "whole tick under jit" path (SURVEY.md §7 design
stance): filters, the three delta-join paths, the revenue closure and the
accumulable SUM reduce compile into a single program. Arrangements are
LSM-leveled (arrangement/lsm.py) with a deterministic merge schedule, so a
tick costs O(delta·log N), not O(N). On a mesh, arrangements are hash-sharded
by their key over the `workers` axis and every key change is an `all_to_all`
exchange (parallel/devicemesh/exchange.py) — the timely-worker config-5 shape
(BASELINE.md) with collectives riding ICI.

All capacities are static (pytree state); overflow flags replace resizing.
The host-orchestrated runtime (dataflow/runtime.py) remains the general
engine; this module is the performance path for the benchmark plan shape.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..arrangement.lsm import (
    LsmAccums,
    LsmBatches,
    accum_lsm_insert,
    accum_lsm_lookup,
    lsm_insert,
    lsm_join,
)
from ..arrangement.spine import arrange_batch
from ..expr import CallBinary, Column, Literal, MapFilterProject
from ..ops.consolidate import compact_to, consolidate, merge_consolidate
from ..ops.reduce import AggregateExpr, _contributions, _emit_output, consolidate_accums
from ..parallel.devicemesh import exchange
from ..repr.batch import UpdateBatch, bucket_cap
from .tpch import BUILDING, Q3_DATE

I64 = np.dtype(np.int64)
RATIO = 8  # LSM merge ratio


def level_caps(full: int, small: int, k: int = 3, ratio: int = RATIO) -> tuple:
    """Geometric level capacities (small, …, full)."""
    caps = [full]
    for _ in range(k - 1):
        caps.append(max(bucket_cap(small), caps[-1] // max(int(ratio), 2)))
    caps.reverse()
    # monotone non-decreasing
    for i in range(1, k):
        caps[i] = max(caps[i], caps[i - 1])
    return tuple(caps)


@dataclass(frozen=True)
class Q3Caps:
    """Static capacities (per shard)."""

    cust: int = 1 << 14
    orders: int = 1 << 15
    lineitem: int = 1 << 16
    delta: int = 1 << 10  # per-tick delta rows per input (pre-exchange)
    bucket: int = 1 << 9  # per-destination exchange bucket
    join_out: int = 1 << 12
    groups: int = 1 << 15
    levels: int = 3
    # value-column dtype: "int32" halves gather/sort/HBM cost on the 32-bit
    # TPU VPU; every TPC-H column fits i32 through SF100 (generator.py).
    # Aggregate accumulators stay i64 regardless.
    val_dtype: str = "int64"

    def arr_levels(self, full: int) -> tuple:
        return level_caps(full, self.delta * 4, self.levels)


@jax.tree_util.register_pytree_node_class
@dataclass
class Q3State:
    cust_by_ck: LsmBatches  # (ck)
    ord_by_ck: LsmBatches  # (ok, ck, od, sp) keyed ck
    ord_by_ok: LsmBatches  # keyed ok
    li_by_ok: LsmBatches  # (lk, ep, dc) keyed lk
    accum: LsmAccums  # key (lk, od, sp) -> sum(rev)

    def tree_flatten(self):
        return (
            (self.cust_by_ck, self.ord_by_ck, self.ord_by_ok, self.li_by_ok, self.accum),
            None,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @staticmethod
    def empty(caps: Q3Caps) -> "Q3State":
        V = np.dtype(caps.val_dtype)
        # the revenue closure multiplies an i32 column by an i64 literal,
        # promoting the aggregate-input (4th grouped val) to i64 — but group
        # KEYS (lk, od, sp) keep the value dtype
        return Q3State(
            cust_by_ck=LsmBatches.empty(caps.arr_levels(caps.cust), (V,), (V,)),
            ord_by_ck=LsmBatches.empty(caps.arr_levels(caps.orders), (V,), (V,) * 4),
            ord_by_ok=LsmBatches.empty(caps.arr_levels(caps.orders), (V,), (V,) * 4),
            li_by_ok=LsmBatches.empty(caps.arr_levels(caps.lineitem), (V,), (V,) * 3),
            accum=LsmAccums.empty(
                caps.arr_levels(caps.groups), (V, V, V), (I64,)
            ),
        )


_CUST_MFP = MapFilterProject(
    3, predicates=(CallBinary("eq", Column(1), Literal(BUILDING)),), projection=(0,)
)
_ORD_MFP = MapFilterProject(
    4, predicates=(CallBinary("lt", Column(2), Literal(Q3_DATE)),), projection=(0, 1, 2, 3)
)
_LI_MFP = MapFilterProject(
    6, predicates=(CallBinary("gt", Column(3), Literal(Q3_DATE)),), projection=(0, 1, 2)
)
# canonical join output: (ck, ok, ck, od, sp, lk, ep, dc)
_CLOSURE = MapFilterProject(
    8,
    map_exprs=(CallBinary("mul", Column(6), CallBinary("sub", Literal(100), Column(7))),),
    projection=(5, 3, 4, 8),  # (lk, od, sp, rev)
)
_AGGS = (AggregateExpr("sum", Column(3)),)


def _maybe_exchange(batch, axis_name, n_shards, bucket):
    """Route to the hash owner, then re-canonicalize (rows from n senders
    interleave). Off-mesh this is the identity: the input is already
    consolidated by arrange_batch."""
    if axis_name is None:
        return batch, jnp.asarray(False)
    out, f = exchange(batch, axis_name, n_shards, bucket)
    return consolidate(out, compact=False), f


def _project_cols(batch: UpdateBatch, perm) -> UpdateBatch:
    return UpdateBatch(
        batch.hashes, (), tuple(batch.vals[i] for i in perm), batch.times, batch.diffs
    )


def _concat_all(batches: list) -> UpdateBatch:
    acc = batches[0]
    for b in batches[1:]:
        acc = UpdateBatch.concat(acc, b)
    return acc


def q3_tick(
    state: Q3State,
    d_cust: UpdateBatch,
    d_ord: UpdateBatch,
    d_li: UpdateBatch,
    time,
    *,
    caps: Q3Caps,
    axis_name: str | None = None,
    n_shards: int = 1,
    with_cust: bool = True,
):
    """One Q3 maintenance tick. Returns (state', out_delta, errs, overflow).

    Raw deltas carry full table schemas; on a mesh each device feeds its own
    slice and rows are routed by key hash. `time` doubles as the LSM merge
    schedule counter, so ticks should be consecutive integers.

    `with_cust=False` compiles a variant with the customer delta path
    statically removed — the analogue of timely not scheduling operators whose
    inputs hold no capabilities; TPC-H RF1/RF2 never touches customer.
    """
    over = jnp.asarray(False)
    jcaps = (caps.join_out,) * caps.levels

    def track(flag):
        nonlocal over
        over = over | flag

    fo, _ = _ORD_MFP.apply(d_ord)
    fl, _ = _LI_MFP.apply(d_li)

    # probe/insert streams skip the compaction sort throughout: dead rows
    # stay inert and these batches are never capacity-shrunk (consolidate.py)
    do_ck = arrange_batch(fo, (1,), compact=False)
    do_ok = arrange_batch(fo, (0,), compact=False)
    dl = arrange_batch(fl, (0,), compact=False)

    do_ck, f = _maybe_exchange(do_ck, axis_name, n_shards, caps.bucket)
    track(f)
    do_ok, f = _maybe_exchange(do_ok, axis_name, n_shards, caps.bucket)
    track(f)
    dl, f = _maybe_exchange(dl, axis_name, n_shards, caps.bucket)
    track(f)

    # intermediate join streams: concat K per-level outputs, O(n)-compact the
    # live rows into one small buffer, and only THEN sort — the r4 profile
    # showed these full-static-capacity sorts were the bulk of tick time
    mid_cap = bucket_cap(2 * caps.join_out)

    def squeeze(batches: list) -> UpdateBatch:
        nonlocal over
        packed, f = compact_to(_concat_all(batches), mid_cap)
        over = over | f
        return packed

    outs = []
    if with_cust:
        fc, _ = _CUST_MFP.apply(d_cust)
        dc = arrange_batch(fc, (0,), compact=False)
        dc, f = _maybe_exchange(dc, axis_name, n_shards, caps.bucket)
        track(f)
        # path 0: d customer ⋈ orders(ck) ⋈ lineitem(ok)
        s0s, f = lsm_join(dc, state.ord_by_ck, jcaps)
        track(f)
        s0 = arrange_batch(squeeze(s0s), (1,), compact=False)  # key ok
        s0, f = _maybe_exchange(s0, axis_name, n_shards, caps.bucket)
        track(f)
        s0s, f = lsm_join(s0, state.li_by_ok, jcaps)
        track(f)
        outs += s0s  # (ck | ok,ck,od,sp | lk,ep,dc) = canonical
        new_cust, f = lsm_insert(state.cust_by_ck, dc, time, RATIO)
        track(f)
    else:
        new_cust = state.cust_by_ck

    # path 1: d orders ⋈ customer(ck) ⋈ lineitem(ok)
    s1s, f = lsm_join(do_ck, new_cust, jcaps)
    track(f)
    s1 = arrange_batch(squeeze(s1s), (0,), compact=False)  # (ok,ck,od,sp | ck): key ok
    s1, f = _maybe_exchange(s1, axis_name, n_shards, caps.bucket)
    track(f)
    s1s, f = lsm_join(s1, state.li_by_ok, jcaps)
    track(f)
    outs += [_project_cols(s, (4, 0, 1, 2, 3, 5, 6, 7)) for s in s1s]
    new_ord_ck, f = lsm_insert(state.ord_by_ck, do_ck, time, RATIO)
    track(f)
    new_ord_ok, f = lsm_insert(state.ord_by_ok, do_ok, time, RATIO)
    track(f)

    # path 2: d lineitem ⋈ orders(ok) ⋈ customer(ck)
    s2s, f = lsm_join(dl, new_ord_ok, jcaps)
    track(f)
    s2 = arrange_batch(squeeze(s2s), (4,), compact=False)  # (lk,ep,dc | ok,ck,od,sp): key ck
    s2, f = _maybe_exchange(s2, axis_name, n_shards, caps.bucket)
    track(f)
    s2s, f = lsm_join(s2, new_cust, jcaps)
    track(f)
    outs += [_project_cols(s, (7, 3, 4, 5, 6, 0, 1, 2)) for s in s2s]
    new_li, f = lsm_insert(state.li_by_ok, dl, time, RATIO)
    track(f)

    # closure + reduce (closure is elementwise — run it on the compacted rows)
    joined, errs1 = _CLOSURE.apply(squeeze(outs))
    grouped = arrange_batch(joined, (0, 1, 2), compact=False)
    grouped, f = _maybe_exchange(grouped, axis_name, n_shards, caps.bucket)
    track(f)

    raw_contrib, errs2 = _contributions(grouped, (0, 1, 2), _AGGS)
    contrib = consolidate_accums(raw_contrib)
    old_accums, old_nrows, missed = accum_lsm_lookup(state.accum, contrib)
    from ..ops.reduce import collision_errs

    errs3 = collision_errs(contrib, missed, time)
    emitted, f = compact_to(_emit_output(contrib, old_accums, old_nrows, time), mid_cap)
    track(f)
    out = consolidate(emitted, compact=False)
    new_accum, f = accum_lsm_insert(state.accum, contrib, time, RATIO)
    track(f)

    # error streams are almost always empty: O(n)-compact the concat into a
    # small buffer before the canonicalizing sort; an overflow of real error
    # rows raises the tick's failure flag (loud, never silently dropped)
    errs_cat, f = compact_to(
        UpdateBatch.concat(UpdateBatch.concat(errs1, errs2), errs3), 8192
    )
    track(f)
    errs = consolidate(errs_cat, compact=False)
    new_state = Q3State(new_cust, new_ord_ck, new_ord_ok, new_li, new_accum)
    # overflow as shape-(1,) so shard_map can concatenate per-device flags
    return new_state, out, errs, over.reshape((1,))


def hydrate(state: Q3State, init_cust, init_ord, init_li, time) -> Q3State:
    """Initial load: place filtered snapshots directly into the TOP level
    (one-time host helper; the per-tick L0 path would overflow on a full
    snapshot, and reference as-of hydration is likewise a bulk path)."""
    fc, _ = _CUST_MFP.apply(init_cust)
    fo, _ = _ORD_MFP.apply(init_ord)
    fl, _ = _LI_MFP.apply(init_li)

    def place(lsm: LsmBatches, keyed: UpdateBatch) -> LsmBatches:
        top = lsm.levels[-1]
        merged = merge_consolidate(top, keyed)
        assert int(merged.count()) <= top.cap, "hydration exceeds top-level cap"
        return LsmBatches(tuple(lsm.levels[:-1]) + (merged.with_capacity(top.cap),))

    state = Q3State(
        cust_by_ck=place(state.cust_by_ck, arrange_batch(fc, (0,))),
        ord_by_ck=place(state.ord_by_ck, arrange_batch(fo, (1,))),
        ord_by_ok=place(state.ord_by_ok, arrange_batch(fo, (0,))),
        li_by_ok=place(state.li_by_ok, arrange_batch(fl, (0,))),
        accum=state.accum,
    )
    # compute the initial aggregate contents through one joined pass:
    # customer ⋈ orders ⋈ lineitem with all arrangements now full, by
    # streaming lineitem through them (single path covers everything since
    # the other deltas are empty).
    dl = arrange_batch(fl, (0,))
    out_cap = bucket_cap(max(int(dl.cap), 256))
    from ..ops.join import join_against

    s = join_against(dl, [b for b in state.ord_by_ok.levels])
    s = consolidate(_concat_all(s)) if s else None
    if s is not None:
        s = arrange_batch(s, (4,))
        s2 = join_against(s, [b for b in state.cust_by_ck.levels])
        s2 = consolidate(_concat_all(s2)) if s2 else None
    else:
        s2 = None
    if s2 is not None:
        canonical = _project_cols(s2, (7, 3, 4, 5, 6, 0, 1, 2))
        joined, _errs = _CLOSURE.apply(canonical)
        grouped = arrange_batch(joined, (0, 1, 2))
        raw_contrib, _e = _contributions(grouped, (0, 1, 2), _AGGS)
        contrib = consolidate_accums(raw_contrib)
        top = state.accum.levels[-1]
        from ..ops.reduce import AccumState

        merged = consolidate_accums(AccumState.concat(top, contrib.with_capacity(contrib.cap)))
        assert int(merged.count()) <= top.cap, "hydration exceeds accum cap"
        state = Q3State(
            state.cust_by_ck,
            state.ord_by_ck,
            state.ord_by_ok,
            state.li_by_ok,
            LsmAccums(tuple(state.accum.levels[:-1]) + (merged.with_capacity(top.cap),)),
        )
    return state


def hydration_output(state: Q3State, time) -> UpdateBatch:
    """The initial contents of the view (all groups, diff +1) after hydrate."""
    from ..ops.reduce import AccumState

    top = state.accum.levels[-1]
    live = top.live
    from ..repr.batch import DIFF_DTYPE, PAD_TIME, to_device_time
    from ..repr.hashing import PAD_HASH

    t = to_device_time(time)
    return UpdateBatch(
        hashes=jnp.where(live, top.hashes, PAD_HASH),
        keys=(),
        vals=tuple(top.keys) + tuple(top.accums),
        times=jnp.where(live, t, PAD_TIME),
        diffs=live.astype(DIFF_DTYPE),
    )


def q3_state_global(caps: Q3Caps, n_shards: int) -> Q3State:
    """Global (unsharded-view) empty state for an n-shard mesh: every array is
    n× the per-shard capacity along axis 0; shard_map splits it evenly."""
    scaled = Q3Caps(
        cust=caps.cust * n_shards,
        orders=caps.orders * n_shards,
        lineitem=caps.lineitem * n_shards,
        delta=caps.delta * n_shards,
        bucket=caps.bucket,
        join_out=caps.join_out * n_shards,
        groups=caps.groups * n_shards,
        levels=caps.levels,
        val_dtype=caps.val_dtype,
    )
    return Q3State.empty(scaled)


def q3_tick_single(caps: Q3Caps, with_cust: bool = True):
    """Single-chip jittable tick: (state, d_cust, d_ord, d_li, t) → …"""
    return partial(q3_tick, caps=caps, axis_name=None, n_shards=1, with_cust=with_cust)


def q3_tick_sharded(mesh, caps: Q3Caps, axis_name: str = "workers"):
    """Mesh-sharded tick via shard_map; inputs/state sharded on axis 0."""
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis_name]
    spec = P(axis_name)
    rep = P()

    def step(state, d_cust, d_ord, d_li, time):
        return q3_tick(
            state, d_cust, d_ord, d_li, time,
            caps=caps, axis_name=axis_name, n_shards=n,
        )

    from ..parallel.devicemesh import mesh_jit

    return mesh_jit(
        step,
        mesh,
        in_specs=(spec, spec, spec, spec, rep),
        out_specs=(spec, spec, spec, spec),
        axis_name=axis_name,
    )
