"""Kernel registry bit-identity: Pallas (interpret mode on CPU) vs XLA oracle.

Every registered kernel (run_sum, multi_take, probe, probe2) must produce
BYTE-identical output to its XLA reference on every input — padding
sentinels, empty batches, deep collision runs included. Tier-1 proves this
on CPU with tiny shapes via ``interpret=True``; the ``kernelbench`` marker
re-runs the same properties at realistic capacities (slow: interpret mode
emulates the kernel op-by-op).

The whole-engine differentials at the bottom force ``kernel_backend =
pallas`` through the dyncfg and replay a TPC-H Q3 hydration and an
insert/delete churn workload, asserting byte-identical peeks AND durable MV
shard contents against the forced-xla run — the acceptance contract of the
pluggable kernel layer.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from materialize_tpu.ops import kernels
from materialize_tpu.ops.kernels.permute import _pallas_multi_take, _xla_multi_take
from materialize_tpu.ops.kernels.probe import (
    _pallas_searchsorted,
    _pallas_searchsorted2,
    _xla_searchsorted,
    _xla_searchsorted2,
)
from materialize_tpu.ops.kernels.segsum import _pallas_run_sum, _xla_run_sum


@pytest.fixture(autouse=True)
def _restore_backend_mode():
    """The kernel mode is process-global state; never leak a forced mode."""
    yield
    kernels.set_kernel_backend("auto")


def _identical(got, want):
    g, w = np.asarray(got), np.asarray(want)
    assert g.dtype == w.dtype and g.shape == w.shape
    assert g.tobytes() == w.tobytes(), (g, w)


# -- registry mechanics -------------------------------------------------------


def test_registry_registers_every_kernel():
    # the PR 15 tick-path trio plus the PR 16 device-mesh routing pair
    assert kernels.registered_kernels() == [
        "bucket_rank",
        "multi_take",
        "probe",
        "probe2",
        "route_dest",
        "run_sum",
    ]


def test_mode_validation_and_resolution():
    with pytest.raises(ValueError):
        kernels.set_kernel_backend("cuda")
    # auto is xla on every platform, by rule (registry.resolve_backend): no
    # registered pallas program compiles for the chip (test_chip_compile.py)
    assert kernels.resolve_backend("auto") == "xla"
    assert kernels.resolve_backend("xla") == "xla"
    assert kernels.resolve_backend("pallas") == "pallas"
    kernels.set_kernel_backend("pallas")
    assert kernels.kernel_backend_mode() == "pallas"
    assert kernels.active_backend() == "pallas"


def test_using_backend_scopes_nest_and_restore():
    kernels.set_kernel_backend("xla")
    with kernels.using_backend("pallas"):
        assert kernels.active_backend() == "pallas"
        with kernels.using_backend("xla"):
            assert kernels.active_backend() == "xla"
        assert kernels.active_backend() == "pallas"
    assert kernels.active_backend() == "xla"
    with pytest.raises(ValueError):
        with kernels.using_backend("auto"):  # a mode, not a backend
            pass


def test_dispatch_bumps_per_backend_counter():
    a = jnp.arange(8, dtype=jnp.uint32)
    q = jnp.asarray([3, 9], dtype=jnp.uint32)
    before = kernels.dispatch_counts()
    with kernels.using_backend("pallas"):
        kernels.dispatch("probe", a, q, side="left")
    after = kernels.dispatch_counts()
    key = ("probe", "pallas")
    assert after.get(key, 0) == before.get(key, 0) + 1


# -- seeded property suites ---------------------------------------------------

TIER1_SIZES = (0, 1, 2, 5, 16, 33, 64)
BENCH_SIZES = (1024, 4096, 8191)


def _run_sum_case(rng, n):
    if n == 0:
        flags = np.zeros(0, dtype=bool)
    else:
        # random run structure: dense runs (collision-bucket shaped), plus
        # the pathological all-one-run and no-run-start-at-0 layouts
        flags = rng.random(n) < rng.choice([0.05, 0.3, 0.9])
        if rng.random() < 0.5 and n > 0:
            flags[0] = True
    cols = (
        rng.integers(-(2**40), 2**40, n).astype(np.int64),  # diff-like
        rng.integers(-(2**20), 2**20, n).astype(np.int32),
        rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32),
    )
    return jnp.asarray(flags), tuple(jnp.asarray(c) for c in cols)


def _check_run_sum(sizes, seed):
    rng = np.random.default_rng(seed)
    for n in sizes:
        for _ in range(3):
            flags, cols = _run_sum_case(rng, n)
            want = _xla_run_sum(flags, cols)
            got = _pallas_run_sum(flags, cols)
            for g, w in zip(got, want):
                _identical(g, w)


def test_run_sum_bit_identical_tier1():
    _check_run_sum(TIER1_SIZES, seed=11)


def test_run_sum_float_columns_fall_back_identically():
    rng = np.random.default_rng(3)
    flags, cols = _run_sum_case(rng, 16)
    cols = cols + (jnp.asarray(rng.random(16), dtype=jnp.float32),)
    for g, w in zip(_pallas_run_sum(flags, cols), _xla_run_sum(flags, cols)):
        _identical(g, w)


def _multi_take_case(rng, n, m):
    cols = (
        rng.integers(0, 2**32, max(n, 1), dtype=np.uint64).astype(np.uint32)[:n],
        rng.integers(-(2**50), 2**50, n).astype(np.int64),
        rng.integers(-(2**50), 2**50, n).astype(np.int64),
        rng.integers(0, 2**31, n).astype(np.uint32),
        (rng.random(n) < 0.5),
        rng.integers(-(2**20), 2**20, n).astype(np.int32),
    )
    idx = rng.integers(0, max(n, 1), m).astype(np.int32)
    return tuple(jnp.asarray(c) for c in cols), jnp.asarray(idx)


def _check_multi_take(sizes, seed):
    rng = np.random.default_rng(seed)
    for n in sizes:
        # gathers from a zero-length source are undefined in the reference
        # too (real batches have pow2 caps >= 8); n == 0 pairs with m == 0
        for m in (0, 1, n, 2 * n + 1) if n else (0,):
            cols, idx = _multi_take_case(rng, n, m)
            want = _xla_multi_take(cols, idx)
            got = _pallas_multi_take(cols, idx)
            for g, w in zip(got, want):
                _identical(g, w)


def test_multi_take_bit_identical_tier1():
    _check_multi_take(TIER1_SIZES, seed=17)


def test_multi_take_empty_cols():
    idx = jnp.asarray([0, 1], dtype=jnp.int32)
    assert _pallas_multi_take((), idx) == ()
    assert _xla_multi_take((), idx) == ()


def _probe_case(rng, n, m):
    a = np.sort(rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32))
    if n > 2 and rng.random() < 0.7:
        # deep collision runs + the all-ones pad sentinel at the tail
        a[n // 2 :] = a[n // 2]
        a[-1] = np.uint32(0xFFFFFFFF)
        a = np.sort(a)
    pool = np.concatenate(
        [a, np.asarray([0, 2**32 - 1], dtype=np.uint32)]
    )
    q = rng.choice(pool, size=m) if m else np.zeros(0, dtype=np.uint32)
    return jnp.asarray(a), jnp.asarray(q.astype(np.uint32))


def _check_probe(sizes, seed):
    rng = np.random.default_rng(seed)
    for n in (s for s in sizes if s > 0):  # search over empty keys undefined
        for m in (0, 1, 7, 65):
            a, q = _probe_case(rng, n, m)
            for side in ("left", "right"):
                _identical(
                    _pallas_searchsorted(a, q, side),
                    _xla_searchsorted(a, q, side),
                )


def test_probe_bit_identical_tier1():
    _check_probe(TIER1_SIZES, seed=23)


def _probe2_case(rng, n, m):
    hi = np.sort(rng.integers(0, 8, n, dtype=np.uint64).astype(np.uint32))
    lo = rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32)
    # sort lexicographically by (hi, lo)
    order = np.lexsort((lo, hi))
    hi, lo = hi[order], lo[order]
    qh = rng.choice(np.concatenate([hi, [np.uint32(3)]]) if n else [np.uint32(0)], size=m)
    ql = rng.choice(np.concatenate([lo, [np.uint32(9)]]) if n else [np.uint32(0)], size=m)
    return tuple(jnp.asarray(x.astype(np.uint32)) for x in (hi, lo, qh, ql))


def _check_probe2(sizes, seed):
    rng = np.random.default_rng(seed)
    for n in (s for s in sizes if s > 0):
        for m in (1, 7, 65):
            hi, lo, qh, ql = _probe2_case(rng, n, m)
            for side in ("left", "right"):
                _identical(
                    _pallas_searchsorted2(hi, lo, qh, ql, side),
                    _xla_searchsorted2(hi, lo, qh, ql, side),
                )


def test_probe2_bit_identical_tier1():
    _check_probe2(TIER1_SIZES, seed=29)


@pytest.mark.slow
@pytest.mark.kernelbench
def test_kernels_bit_identical_at_capacity():
    """The same properties at realistic tick capacities (interpret mode)."""
    _check_run_sum(BENCH_SIZES, seed=101)
    _check_multi_take(BENCH_SIZES, seed=103)
    _check_probe(BENCH_SIZES, seed=107)
    _check_probe2(BENCH_SIZES, seed=109)


# -- op-level composition: consolidate through a forced backend ---------------


def test_consolidate_forced_pallas_matches_xla():
    from materialize_tpu.repr.batch import UpdateBatch
    from materialize_tpu.repr.hashing import hash_columns
    from materialize_tpu.ops.consolidate import consolidate

    rng = np.random.default_rng(41)
    n = 64
    keys = (jnp.asarray(rng.integers(0, 6, n).astype(np.int64)),)
    vals = (jnp.asarray(rng.integers(-5, 5, n).astype(np.int64)),)
    hashes = hash_columns(keys)
    times = jnp.asarray(rng.integers(0, 3, n).astype(np.uint32))
    diffs = jnp.asarray(rng.integers(-2, 3, n).astype(np.int64))
    b = UpdateBatch(hashes, keys, vals, times, diffs)

    kernels.set_kernel_backend("xla")
    want = consolidate(b)
    kernels.set_kernel_backend("pallas")
    got = consolidate(b)
    for g, w in zip(
        (got.hashes, *got.keys, *got.vals, got.times, got.diffs),
        (want.hashes, *want.keys, *want.vals, want.times, want.diffs),
    ):
        _identical(g, w)


# -- whole-engine differentials: forced pallas vs forced xla ------------------


def _q3_rows(backend):
    import tpch_q3
    from materialize_tpu.adapter import Coordinator

    c = Coordinator()
    c.execute(f"ALTER SYSTEM SET kernel_backend = {backend}")
    c.execute(tpch_q3.SOURCE_SQL)
    c.execute(tpch_q3.VIEW_SQL)
    for _ in range(3):
        c.advance()
    rows = sorted(c.execute("SELECT * FROM q3").rows)
    counts = kernels.dispatch_counts()
    return rows, counts


@pytest.mark.slow
def test_q3_hydration_forced_pallas_byte_identical():
    """TPC-H Q3 hydration + refresh ticks under kernel_backend=pallas: every
    peeked row equals the forced-xla run exactly, and the dispatch counter
    proves the pallas path actually served the traces."""
    want, _ = _q3_rows("xla")
    got, counts = _q3_rows("pallas")
    assert got == want
    assert any(b == "pallas" and c > 0 for (_k, b), c in counts.items()), counts


def _churn_workload(data_dir, backend):
    """8 churn ticks over a join+group MV; returns peeks and the net durable
    shard contents (tests/test_shared_arrangements.py shape)."""
    from materialize_tpu.adapter import Coordinator

    c = Coordinator(data_dir=data_dir)
    c.execute(f"ALTER SYSTEM SET kernel_backend = {backend}")
    c.execute("CREATE TABLE t1 (k int, a int)")
    c.execute("CREATE TABLE t2 (k int, b int)")
    c.execute(
        "CREATE MATERIALIZED VIEW mv_join AS"
        " SELECT t1.k AS k, a, b FROM t1, t2 WHERE t1.k = t2.k"
    )
    c.execute(
        "CREATE MATERIALIZED VIEW mv_grp AS"
        " SELECT t1.k AS k, sum(b) AS sb FROM t1, t2 WHERE t1.k = t2.k"
        " GROUP BY t1.k"
    )
    c.execute("INSERT INTO t1 VALUES (1, 10), (2, 20), (3, 30)")
    c.execute("INSERT INTO t2 VALUES (1, 100), (2, 200), (2, 201)")
    c.execute("INSERT INTO t1 VALUES (4, 40)")
    c.execute("INSERT INTO t2 VALUES (4, 400), (3, 300)")
    c.execute("DELETE FROM t2 WHERE b = 201")
    c.execute("INSERT INTO t1 VALUES (5, 50)")
    c.execute("DELETE FROM t1 WHERE k = 2")
    c.execute("INSERT INTO t2 VALUES (5, 500), (1, 101)")
    peeks = {
        "mv_join": sorted(c.execute("SELECT * FROM mv_join").rows),
        "mv_grp": sorted(c.execute("SELECT * FROM mv_grp").rows),
        "adhoc": sorted(
            c.execute("SELECT a, b FROM t1, t2 WHERE t1.k = t2.k").rows
        ),
    }
    shards = {}
    for name in ("mv_join", "mv_grp"):
        gid = c.catalog.get(name).global_id
        m = c._shard(gid)
        _seq, state = m.fetch_state()
        net: dict = {}
        for cols in m.snapshot(state.upper - 1):
            ncols = len([k for k in cols if k.startswith("c")])
            for row in zip(
                *([cols[f"c{i}"] for i in range(ncols)] + [cols["diffs"]])
            ):
                key = tuple(int(v) for v in row[:-1])
                net[key] = net.get(key, 0) + int(row[-1])
        shards[name] = {k: v for k, v in net.items() if v != 0}
    return peeks, shards


def test_churn_forced_pallas_byte_identical_peeks_and_shards(tmp_path):
    peeks_x, shards_x = _churn_workload(str(tmp_path / "xla"), "xla")
    peeks_p, shards_p = _churn_workload(str(tmp_path / "pallas"), "pallas")
    assert peeks_p == peeks_x
    assert shards_p == shards_x


def test_kernel_backend_flip_mid_stream(tmp_path):
    """Flipping the dyncfg mid-workload changes the serving backend at the
    next render with no restart — and results stay byte-identical."""
    from materialize_tpu.adapter import Coordinator

    c = Coordinator()
    c.execute("CREATE TABLE t (k int, v int)")
    c.execute(
        "CREATE MATERIALIZED VIEW s AS SELECT k, sum(v) FROM t GROUP BY k"
    )
    c.execute("INSERT INTO t VALUES (1, 5), (2, 7)")
    r1 = sorted(c.execute("SELECT * FROM s").rows)
    before = kernels.dispatch_counts()
    c.execute("ALTER SYSTEM SET kernel_backend = pallas")
    c.execute("INSERT INTO t VALUES (1, 3), (3, 11)")
    r2 = sorted(c.execute("SELECT * FROM s").rows)
    after = kernels.dispatch_counts()
    assert r1 == [(1, 5), (2, 7)]
    assert r2 == [(1, 8), (2, 7), (3, 11)]
    pallas_traces = lambda d: sum(
        v for (_k, b), v in d.items() if b == "pallas"
    )
    assert pallas_traces(after) > pallas_traces(before)
    # flip back: subsequent renders serve from xla again (group 2 still has
    # two live rows, so its zero sum stays in the output)
    c.execute("ALTER SYSTEM SET kernel_backend = xla")
    c.execute("INSERT INTO t VALUES (2, -7)")
    assert sorted(c.execute("SELECT * FROM s").rows) == [(1, 8), (2, 0), (3, 11)]


def test_invalid_kernel_backend_rejected():
    from materialize_tpu.adapter import Coordinator

    c = Coordinator()
    with pytest.raises(Exception, match="kernel_backend"):
        c.execute("ALTER SYSTEM SET kernel_backend = cuda")
    # the config (and the process-global mode) kept its previous value
    assert c.configs.get("kernel_backend") == "auto"


def test_mz_kernel_dispatch_introspection():
    from materialize_tpu.adapter import Coordinator

    c = Coordinator()
    c.execute("CREATE TABLE t (v int)")
    c.execute("CREATE MATERIALIZED VIEW s AS SELECT sum(v) FROM t")
    c.execute("INSERT INTO t VALUES (1), (2)")
    c.execute("SELECT * FROM s")
    rows = c.execute("SELECT * FROM mz_kernel_dispatch").rows
    kers = {r[0] for r in rows}
    assert kers & {"run_sum", "multi_take", "probe", "probe2"}
    assert all(r[1] in ("xla", "pallas") and r[2] > 0 for r in rows)
