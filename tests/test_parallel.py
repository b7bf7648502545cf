"""Mesh sharding: exchange routing, sharded fused Q3 vs single-chip vs oracle.

Runs on the 8-device virtual CPU mesh (conftest), the stand-in for real
multi-chip ICI (SURVEY.md §4 multi-node-without-a-cluster strategy).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from materialize_tpu.models import tpch
from materialize_tpu.models.fused_q3 import (
    Q3Caps,
    Q3State,
    q3_state_global,
    q3_tick_sharded,
    q3_tick_single,
)
from materialize_tpu.parallel import exchange, make_mesh
from materialize_tpu.repr import PAD_HASH, UpdateBatch
from materialize_tpu.storage import TpchGenerator


@pytest.mark.smoke
def test_route_and_exchange_roundtrip():
    """Every live row lands on the device owning hash % n, none are lost."""
    mesh = make_mesh(4)

    k = np.arange(64, dtype=np.int64)
    batch = UpdateBatch.build((), (k, k * 10), np.zeros(64), np.ones(64, dtype=np.int64))
    from materialize_tpu.arrangement import arrange_batch

    keyed = arrange_batch(batch, (0,))
    # replicate the batch split across 4 devices (each sends a quarter)
    from jax.sharding import PartitionSpec as P

    try:
        shard_map = jax.shard_map
    except AttributeError:
        from jax.experimental.shard_map import shard_map

    def go(b):
        out, over = exchange(b, "workers", 4, 32)
        return out, over.reshape((1,))

    f = jax.jit(
        shard_map(go, mesh=mesh, in_specs=(P("workers"),), out_specs=(P("workers"), P("workers")))
    )
    out, over = f(keyed)
    assert not bool(np.asarray(over).any())
    hashes = np.asarray(out.hashes)
    diffs = np.asarray(out.diffs)
    live = (hashes != np.uint64(PAD_HASH)) & (diffs != 0)
    assert live.sum() == 64  # nothing lost
    # rows grouped per receiving device: check ownership
    per_dev = hashes.reshape(4, -1)
    live_dev = live.reshape(4, -1)
    for d in range(4):
        owned = per_dev[d][live_dev[d]] % 4
        assert (owned == d).all()


@pytest.mark.parametrize(
    "n_shards,val_dtype",
    [
        (1, "int64"),
        (1, "int32"),
        # the multi-shard case is in the smoke gate: it is the cheapest test
        # that traces the fused engine under shard_map, which is where the
        # round-4 carry-varyingness regression slipped through
        pytest.param(4, "int32", marks=pytest.mark.smoke),
    ],
)
def test_fused_q3_matches_oracle(n_shards, val_dtype):
    # delta sized so tick-based hydration fits in L0 (= 4*delta per shard);
    # int32 is the bench-path value dtype (bench.py) and must match the
    # oracle exactly, not just approximately
    delta = 1 << 10 if n_shards == 1 else 1 << 8
    caps = Q3Caps(cust=1 << 10, orders=1 << 10, lineitem=1 << 12, delta=delta,
                  bucket=1 << 9, join_out=1 << 12, groups=1 << 11,
                  val_dtype=val_dtype)
    gen = TpchGenerator(sf=0.0005, seed=11, val_dtype=np.dtype(val_dtype), columns=tpch.Q3_COLUMNS)
    init = gen.initial_batches(1)

    def pad_to(b, cap):
        return b.with_capacity(max(cap, b.cap))

    if n_shards == 1:
        state = Q3State.empty(caps)
        step = jax.jit(q3_tick_single(caps))
    else:
        mesh = make_mesh(n_shards)
        state = q3_state_global(caps, n_shards)
        step = q3_tick_sharded(mesh, caps)

    out_acc = {}

    def run(t, dc, do, dl):
        nonlocal state
        mult = n_shards
        dc = dc.with_capacity(_ceil_mult(dc.cap, mult))
        do = do.with_capacity(_ceil_mult(do.cap, mult))
        dl = dl.with_capacity(_ceil_mult(dl.cap, mult))
        state, out, errs, over = step(state, dc, do, dl, t)
        assert not bool(np.asarray(over).any()), "capacity overflow"
        assert int(errs.count()) == 0
        for data, tt, d in out.to_rows():
            out_acc[data] = out_acc.get(data, 0) + d

    empty_c = UpdateBatch.empty(8 * n_shards, (), (np.dtype(val_dtype),) * 3)
    empty_o = UpdateBatch.empty(8 * n_shards, (), (np.dtype(val_dtype),) * 4)
    empty_l = UpdateBatch.empty(8 * n_shards, (), (np.dtype(val_dtype),) * 6)

    run(1, init["customer"], init["orders"], init["lineitem"])
    # refreshes ride at the hydration tick's input capacities: one compile of
    # the whole-tick program per case, not one per input shape
    for t in range(2, 5):
        ref = gen.refresh(t, frac=0.02)
        run(
            t,
            pad_to(empty_c, init["customer"].cap),
            pad_to(ref["orders"], init["orders"].cap),
            pad_to(ref["lineitem"], init["lineitem"].cap),
        )

    integrated = {k: v for k, v in out_acc.items() if v != 0}
    want = tpch.q3_oracle(*tpch.q3_inputs(gen.live()))
    want = {k: v for k, v in want.items() if v != 0}
    got = {}
    for (lk, od, sp, rev), cnt in integrated.items():
        assert cnt == 1
        got[(lk, od, sp)] = rev
    assert got == want


def _ceil_mult(n, m):
    return ((n + m - 1) // m) * m


@pytest.mark.smoke
@pytest.mark.slow
def test_sharded_fused_sql_matches_host_and_single():
    """SQL-defined MV on a 4-shard mesh == single-device fused == host runtime.

    The general engine's multi-worker mode (VERDICT r3 #3): SQL text → LIR →
    FusedDataflow under shard_map, not the hand-built Q3 model."""
    from materialize_tpu.adapter import Coordinator
    from materialize_tpu.dataflow.fused import FusedDataflow

    host = Coordinator()
    single = Coordinator()
    single.execute("ALTER SYSTEM SET enable_fused_render = true")
    sharded = Coordinator(mesh=make_mesh(4))
    sharded.execute("ALTER SYSTEM SET enable_fused_render = true")
    cs = (host, single, sharded)

    def both(sql):
        return [c.execute(sql) for c in cs]

    def check(sql):
        r = both(sql)
        assert sorted(r[0].rows) == sorted(r[1].rows) == sorted(r[2].rows), (
            sql, r[0].rows, r[1].rows, r[2].rows,
        )
        return r[0].rows

    both("CREATE TABLE c (ck int, seg int)")
    both("CREATE TABLE o (ok int, ck int, od int)")
    both("CREATE TABLE l (lk int, price int)")
    both(
        "CREATE MATERIALIZED VIEW q3 AS SELECT o.ok, sum(l.price), count(*) "
        "FROM c, o, l WHERE c.ck = o.ck AND o.ok = l.lk AND c.seg = 1 "
        "AND o.od < 50 GROUP BY o.ok"
    )
    # the sharded coordinator must actually be running a mesh FusedDataflow
    dfs = [df for _g, df, _s in sharded.dataflows]
    assert dfs and isinstance(dfs[0], FusedDataflow) and dfs[0].n_shards == 4

    import random

    rng = random.Random(23)
    for i in range(5):
        both(f"INSERT INTO c VALUES ({i}, {rng.randrange(2)})")
        both(
            f"INSERT INTO o VALUES ({i * 10}, {rng.randrange(5)}, "
            f"{rng.randrange(100)})"
        )
        both(
            f"INSERT INTO l VALUES ({rng.randrange(5) * 10}, {rng.randrange(500)})"
        )
        if i >= 2:
            both(f"DELETE FROM l WHERE lk = {rng.randrange(5) * 10}")
        check("SELECT * FROM q3")
