"""Accumulable reduce (SUM/COUNT) vs NumPy oracle across ticks with retractions."""

import numpy as np

from materialize_tpu.expr import Column, Literal
from materialize_tpu.ops.reduce import (
    AccumState,
    AggregateExpr,
    accumulable_step,
    consolidate_accums,
)
from materialize_tpu.repr import UpdateBatch, bucket_cap


def mkbatch(cols, times, diffs):
    return UpdateBatch.build(
        (), tuple(np.asarray(c, dtype=np.int64) for c in cols), times, diffs
    )


AGGS = (
    AggregateExpr("sum", Column(1)),
    AggregateExpr("count", Literal(1)),
)


def run_ticks(ticks):
    """ticks: list of (keys, vals, diffs). Returns accumulated output dict + state."""
    state = AccumState.empty(8, (np.dtype(np.int64),), (np.dtype(np.int64), np.dtype(np.int64)))
    out_acc = {}
    for t, (ks, vs, ds) in enumerate(ticks):
        delta = mkbatch([ks, vs], [t] * len(ks), ds)
        state, out, _errs, _counts = accumulable_step(state, delta, (0,), AGGS, t)
        n = int(state.count())
        state = consolidate_accums(state).with_capacity(bucket_cap(n))
        for data, tt, d in out.to_rows():
            out_acc[(data, tt)] = out_acc.get((data, tt), 0) + d
    return {k: v for k, v in out_acc.items() if v != 0}, state


def oracle(ticks):
    """Integrated final groups + per-tick expected output deltas."""
    groups = {}
    out = {}

    def snapshot():
        # a group is present iff its count is positive (matches the engine's
        # old_nrows > 0 / new_nrows > 0 presence rule)
        return {
            k: (sum(v for v, _ in rows), sum(c for _, c in rows))
            for k, rows in groups.items()
            if sum(c for _, c in rows) > 0
        }

    prev = {}
    for t, (ks, vs, ds) in enumerate(ticks):
        for k, v, d in zip(ks, vs, ds):
            groups.setdefault(int(k), []).append((int(v) * d, d))
        cur = snapshot()
        for k in set(prev) | set(cur):
            if prev.get(k) != cur.get(k):
                if k in prev:
                    out[((k,) + prev[k], t)] = out.get(((k,) + prev[k], t), 0) - 1
                if k in cur:
                    out[((k,) + cur[k], t)] = out.get(((k,) + cur[k], t), 0) + 1
        prev = cur
    return {k: v for k, v in out.items() if v != 0}


def test_sum_count_single_tick():
    got, state = run_ticks([([1, 1, 2], [10, 5, 7], [1, 1, 1])])
    assert got == {((1, 15, 2), 0): 1, ((2, 7, 1), 0): 1}
    assert int(state.count()) == 2


def test_sum_count_update_and_retract():
    ticks = [
        ([1, 2], [10, 20], [1, 1]),
        ([1], [5], [1]),  # group 1: sum 15, count 2
        ([1, 1], [10, 5], [-1, -1]),  # group 1 emptied
    ]
    got, state = run_ticks(ticks)
    assert got == {
        ((1, 10, 1), 0): 1,
        ((2, 20, 1), 0): 1,
        ((1, 10, 1), 1): -1,
        ((1, 15, 2), 1): 1,
        ((1, 15, 2), 2): -1,
    }
    assert int(state.count()) == 1  # only group 2 remains


def test_noop_tick_emits_nothing():
    ticks = [
        ([1], [10], [1]),
        ([1, 1], [3, -3], [1, 1]),  # sum unchanged? no: count changes
    ]
    got, _ = run_ticks(ticks)
    # tick1: sum stays 10 but count 1->3, so output changes
    assert ((1, 10, 1), 1) in got and got[((1, 10, 1), 1)] == -1
    assert got[((1, 10, 3), 1)] == 1


def test_sum_error_routes_to_err_stream():
    """Division by zero inside SUM contributes nothing and lands in errs."""
    from materialize_tpu.expr import CallBinary

    aggs = (AggregateExpr("sum", CallBinary("div", Column(1), Column(2))),)
    state = AccumState.empty(8, (np.dtype(np.int64),), (np.dtype(np.int64),))
    delta = mkbatch([[1, 1], [10, 7], [2, 0]], [0, 0], [1, 1])
    state, out, errs, _counts = accumulable_step(state, delta, (0,), aggs, 0)
    assert [r[0] for r in out.to_rows()] == [(1, 5)]  # only the clean row
    err_rows = errs.to_rows()
    assert len(err_rows) == 1 and err_rows[0][2] == 1  # one err row, diff 1


def test_random_many_ticks_vs_oracle(rng):
    ticks = []
    for _ in range(8):
        n = int(rng.integers(1, 30))
        ks = rng.integers(0, 6, n).astype(np.int64)
        vs = rng.integers(-20, 20, n).astype(np.int64)
        ds = rng.integers(-1, 3, n)
        ticks.append((ks, vs, ds))
    got = run_ticks(ticks)[0]
    want = oracle(ticks)
    assert got == want


def test_hash_bucket_overflow_detected_not_silent():
    """Keys sharing one hash beyond even the WIDENED scan must raise an
    error row, never silently treat the probe as absent. (Buckets past the
    narrow scan but within _WIDE_HASH_COLLISIONS now resolve via probe
    widening — tests/test_collisions.py.)"""
    import jax.numpy as jnp

    from materialize_tpu.expr.scalar import EvalErr
    from materialize_tpu.ops.reduce import (
        _WIDE_HASH_COLLISIONS,
        collision_errs,
        lookup_accums,
    )

    n = _WIDE_HASH_COLLISIONS + 1
    cap = 128
    # fabricate a state whose first n entries share one hash but hold
    # distinct keys 0..n-1 (a synthetic 64-bit collision pileup)
    from materialize_tpu.repr.hashing import PAD_HASH

    hashes = jnp.full((cap,), PAD_HASH, dtype=jnp.uint64).at[:n].set(jnp.uint64(42))
    keys = (jnp.arange(cap, dtype=jnp.int64),)
    accums = (jnp.full((cap,), 7, dtype=jnp.int64),)
    nrows = jnp.ones((cap,), dtype=jnp.int64)
    state = AccumState(hashes, keys, accums, nrows)

    # probe for the last colliding key — beyond the scan width
    p_hashes = jnp.full((cap,), PAD_HASH, dtype=jnp.uint64).at[0].set(jnp.uint64(42))
    p_keys = (jnp.zeros((cap,), dtype=jnp.int64).at[0].set(n - 1),)
    probe = AccumState(p_hashes, p_keys, (jnp.zeros((cap,), dtype=jnp.int64),), jnp.ones((cap,), dtype=jnp.int64))

    found, _accs, _nrows, missed = lookup_accums(state, probe)
    assert not bool(found[0])
    assert bool(missed[0]), "unresolved bucket probe must be flagged"

    errs = collision_errs(probe, missed, 3)
    rows = errs.to_rows()
    assert rows and rows[0][0] == (int(EvalErr.HASH_COLLISION_EXHAUSTED),)

    # a probe for a key INSIDE the scan width resolves and is not flagged
    p_keys2 = (jnp.zeros((cap,), dtype=jnp.int64).at[0].set(0),)
    probe2 = AccumState(p_hashes, p_keys2, (jnp.zeros((cap,), dtype=jnp.int64),), jnp.ones((cap,), dtype=jnp.int64))
    found2, accs2, _n2, missed2 = lookup_accums(state, probe2)
    assert bool(found2[0]) and not bool(missed2[0])
    assert int(accs2[0][0]) == 7
