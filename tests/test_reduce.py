"""Accumulable reduce (SUM/COUNT) vs NumPy oracle across ticks with retractions."""

import jax
import numpy as np
import pytest

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


# --- the step's table update: a merge of two sorted tables -------------------

I32 = np.dtype(np.int32)
I64 = np.dtype(np.int64)


def _step_batch(keys, vals, diffs, t, cap):
    """Rows (key columns ..., value) at time t: keys is one tuple per row."""
    n = len(vals)
    cols = [np.asarray([k[i] for k in keys], dtype=dt) for i, dt in enumerate((I64, I32))]
    return UpdateBatch.build(
        (), (*cols, np.asarray(vals, dtype=I64)), [t] * n, np.asarray(diffs), cap=cap
    )


_STEP_AGGS = (AggregateExpr("sum", Column(2)), AggregateExpr("count", Literal(1)))


def _former_step(state, delta, key_cols, aggs, time):
    """`accumulable_step` as it was before its table update became a merge:
    the consolidated delta concatenated to the table and sorted again."""
    from materialize_tpu.ops.consolidate import consolidate
    from materialize_tpu.ops.reduce import (
        _contributions,
        _emit_output,
        _step_counts,
        collision_errs,
        lookup_accums,
    )

    raw, errs = _contributions(delta, key_cols, aggs)
    contrib = consolidate_accums(raw)
    _found, old_accums, old_nrows, missed = lookup_accums(state, contrib)
    out = consolidate(_emit_output(contrib, old_accums, old_nrows, time, aggs, keyless=not key_cols))
    errs = consolidate(UpdateBatch.concat(errs, collision_errs(contrib, missed, time)))
    new_state = consolidate_accums(AccumState.concat(state, contrib))
    counts = _step_counts(new_state, contrib, old_nrows, errs, new_state.count() < 0)
    return new_state, out, errs, counts


def _new_step(kind):
    from materialize_tpu.expr.linear import MapFilterProject
    from materialize_tpu.ops.fused_reduce import fused_mfp_reduce_step

    if kind == "accumulable":
        return lambda s, d, k, a, t: accumulable_step(s, d, k, a, t)
    return lambda s, d, k, a, t: fused_mfp_reduce_step(s, d, t, MapFilterProject.identity(3), k, a)


def _same_arrays(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# (key columns, table capacity, delta capacity, rows of the second delta)
_MERGE_CASES = {
    "i64_table_longer": ((0,), 256, 16, 16),
    "two_keys_table_longer": ((0, 1), 256, 16, 16),
    "i64_delta_longer": ((0,), 8, 256, 200),
    "keyless_delta_longer": ((), 8, 256, 200),
    "i64_bulk_slice": ((0,), 256, 256, 200),
    "two_keys_empty_delta": ((0, 1), 256, 16, 0),
    "keyless_empty_delta": ((), 8, 16, 0),
}


@pytest.mark.parametrize("kind", ["fused", "accumulable"])
@pytest.mark.parametrize("case", list(_MERGE_CASES))
def test_step_table_merge_is_bit_identical_to_the_former_sort(case, kind):
    """Both reduce steps merge their consolidated delta into their table
    (`merge_consolidate_accums`): table, output, error batch and the first
    three counts are the former concatenate-and-sort step's bit for bit, and
    the fourth count (a double collision) is 0. The second delta makes groups
    appear, vanish (every row retracted) and change."""
    key_cols, state_cap, delta_cap, n2 = _MERGE_CASES[case]
    rng = np.random.default_rng(sum(map(ord, case)))
    step = _new_step(kind)
    state = AccumState.empty(state_cap, (I64, I32)[: len(key_cols)], (I64, I64))

    # the first delta: groups 0..g-1, three rows each, fitting the table
    g = min(state_cap, 60) if key_cols else 1
    keys1 = [(int(k) * 7 - 90, int(k) % 5) for k in np.repeat(np.arange(g), 3)]
    vals1 = rng.integers(-50, 50, len(keys1)).tolist()
    d1 = _step_batch(keys1, vals1, [1] * len(keys1), 1, max(delta_cap, bucket_cap(len(keys1))))
    state, *_ = _former_step(state, d1, key_cols, _STEP_AGGS, 1)
    state = state.with_capacity(state_cap)

    # the second: retract every row of the first groups, add to others, add new ones
    keys2, vals2, diffs2 = [], [], []
    vanish = min(g, max(1, n2 // 6))
    for i in range(3 * vanish):
        if len(keys2) < n2:
            keys2.append(keys1[i]), vals2.append(vals1[i]), diffs2.append(-1)
    while len(keys2) < n2:
        k = int(rng.integers(0, 3 * g + n2))
        keys2.append((k * 7 - 90, k % 5)), vals2.append(int(rng.integers(-50, 50)))
        diffs2.append(int(rng.choice([1, 1, 2, -1])) if k < g else 1)
    d2 = _step_batch(keys2, vals2, diffs2, 2, delta_cap)

    got = step(state, d2, key_cols, _STEP_AGGS, 2)
    want = _former_step(state, d2, key_cols, _STEP_AGGS, 2)
    for x, y in zip(got[:3], want[:3]):
        _same_arrays(x, y)
    assert np.asarray(got[3]).tolist() == np.asarray(want[3])[:3].tolist() + [0]
    assert got[0].cap == state_cap + delta_cap
    if n2:
        assert int(np.asarray(got[3])[1]) > 0  # groups changed


@pytest.fixture
def one_packed_key(monkeypatch):
    """Every group of a reduce under ONE packed (hash, mix) pair, so any two
    keys are a double collision: reduce's key hash and `_accum_pack` patched,
    the jit caches cleared on both sides so no program traced under the patch
    outlives the test."""
    import jax.numpy as jnp

    from materialize_tpu.ops import reduce

    jax.clear_caches()
    monkeypatch.setattr(reduce, "hash_columns", lambda cols: jnp.full(cols[0].shape, 42, jnp.uint32))
    monkeypatch.setattr(reduce, "_accum_pack", lambda s: (s.hashes, jnp.zeros_like(s.hashes)))
    yield
    monkeypatch.undo()
    jax.clear_caches()


def _collision_rows(errs):
    from materialize_tpu.expr.scalar import EvalErr

    code = int(EvalErr.HASH_COLLISION_EXHAUSTED)
    return [(r[1], r[2]) for r in errs.to_rows() if r[0] == (code,)] if errs is not None else []


@pytest.mark.parametrize("kind", ["fused", "accumulable", "shared_trace"])
def test_a_packed_key_double_collision_is_an_error_row_never_a_wrong_sum(one_packed_key, kind):
    """Keys 1 and 2 share the packed pair: the table holds them in key order,
    and a delta for key 1 merges in behind key 2, where no run merge can
    reach it. The step flags it: one HASH_COLLISION_EXHAUSTED row at the
    step's time in its error batch, and the fourth count set, which the host
    reads as `collided`. The first step, into an empty table, flags nothing."""
    from materialize_tpu.arrangement.trace_manager import SharedReduceTrace
    from materialize_tpu.dataflow.runtime import _reduce_in_slices
    from materialize_tpu.ops.reduce import read_step_counts

    d1 = _step_batch([(1, 0), (2, 0)], [10, 20], [1, 1], 1, 8)
    d2 = _step_batch([(1, 0)], [5], [1], 2, 8)
    if kind == "shared_trace":
        trace = SharedReduceTrace("g", (0,), _STEP_AGGS, (I64, I32, I64), exporter="x")
        _out, errs = trace.step(1, d1, _reduce_in_slices)
        assert not trace.collided and _collision_rows(errs) == []
        _out, errs = trace.step(2, d2, _reduce_in_slices)
        assert trace.collided and _collision_rows(errs) == [(2, 1)]
        return
    step = _new_step(kind)
    state = AccumState.empty(8, (I64,), (I64, I64))
    state, _out, errs, counts = step(state, d1, (0,), _STEP_AGGS, 1)
    assert read_step_counts(counts, errs)[1:] == (2, None, False)
    state, _out, errs, counts = step(state.with_capacity(8), d2, (0,), _STEP_AGGS, 2)
    groups, _changed, errs, collided = read_step_counts(counts, errs)
    assert collided and _collision_rows(errs) == [(2, 1)]
    assert groups == 3  # key 1 twice: the table the error row stands for


def _state_merges() -> dict:
    """{(dataflow, outcome): count} of `mzt_reduce_state_merges_total` (the
    registry is the process's: a view of another test may share the id)."""
    from materialize_tpu.obs.metrics import REGISTRY

    out: dict = {}
    for fam in REGISTRY.families():
        if fam.name == "mzt_reduce_state_merges_total":
            for labels, v in fam.samples:
                key = (dict(labels)["dataflow"], dict(labels)["outcome"])
                out[key] = out.get(key, 0) + v
    return out


def _merges_since(before: dict, dataflow: str) -> dict:
    now = _state_merges()
    return {o: now.get((dataflow, o), 0) - before.get((dataflow, o), 0) for o in ("merged", "collision")}


@pytest.mark.parametrize(
    "view",
    [
        "SELECT k, sum(a) AS s FROM t GROUP BY k",
        # a string function keeps the MFP its own node, so the reduce is not fused
        "SELECT k, sum(a) AS s FROM t WHERE upper(s) = 'X' GROUP BY k",
    ],
    ids=["fused", "private"],
)
def test_a_served_view_fails_on_a_merge_collision_and_counts_it(one_packed_key, view):
    """Through SQL: every step that had input counts one table merge; the
    step whose merge met the double collision counts `collision`, and the
    view's SELECT fails with the collision's error instead of reading a sum."""
    from materialize_tpu.adapter import Coordinator
    from materialize_tpu.dataflow import runtime
    from materialize_tpu.errors import sqlstate_of

    c = Coordinator()
    c.execute("CREATE TABLE t (k int, a int, s text)")
    c.execute("INSERT INTO t VALUES (1, 10, 'x'), (2, 20, 'x')")
    before = _state_merges()
    c.execute(f"CREATE MATERIALIZED VIEW v AS {view}")
    gid = c.catalog.get("v").global_id
    df = next(d for g, d, _ in c.dataflows if g == gid)
    (node,) = [n for _, ops, _ in df.builds for n, _ in ops if isinstance(n, runtime._REDUCE_NODES)]
    assert isinstance(node, runtime.FusedMfpReduceNode if "upper" not in view else runtime.ReduceNode)
    assert sorted(c.execute("SELECT * FROM v").rows) == [(1, 10), (2, 20)]
    assert _merges_since(before, gid) == {"merged": 1, "collision": 0}  # hydration
    before = _state_merges()
    c.execute("INSERT INTO t VALUES (1, 5, 'x')")
    assert _merges_since(before, gid) == {"merged": 0, "collision": 1}
    with pytest.raises(Exception) as caught:
        c.execute("SELECT * FROM v")
    assert sqlstate_of(caught.value) == "XX000" and "hash collision exhausted" in str(caught.value)
