"""The hot-path primitives against plain NumPy, bit for bit.

`run_sum` (ops/consolidate.py), `multi_take` (ops/permute.py), `searchsorted`
and `searchsorted2` (ops/search.py), `route_dest` and `bucket_rank`
(parallel/devicemesh/exchange.py) each have ONE implementation, the XLA
lowering every tick runs. The oracles below are written here and share no
code with them. Cases are seeded and cover what the engine feeds them: dense
runs, the all-one-run and no-run-start-at-0 layouts, deep collision runs, the
all-ones pad sentinel, empty and float columns.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from materialize_tpu.ops.consolidate import run_sum
from materialize_tpu.ops.permute import multi_take
from materialize_tpu.ops.search import searchsorted, searchsorted2
from materialize_tpu.parallel.devicemesh.exchange import bucket_rank, route_dest

TIER1_SIZES = (0, 1, 2, 5, 16, 33, 64)
BENCH_SIZES = (1024, 4096, 8191)


def _identical(got, want):
    g, w = np.asarray(got), np.asarray(want)
    assert g.dtype == w.dtype and g.shape == w.shape
    assert g.tobytes() == w.tobytes(), (g, w)


# -- seeded case generators ---------------------------------------------------


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
    return flags, cols


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
    return cols, idx


def _probe_case(rng, n, m):
    a = np.sort(rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32))
    if n > 2 and rng.random() < 0.7:
        # deep collision runs + the all-ones pad sentinel at the tail
        a[n // 2 :] = a[n // 2]
        a[-1] = np.uint32(0xFFFFFFFF)
        a = np.sort(a)
    pool = np.concatenate([a, np.asarray([0, 2**32 - 1], dtype=np.uint32)])
    q = rng.choice(pool, size=m) if m else np.zeros(0, dtype=np.uint32)
    return a, q.astype(np.uint32)


def _probe2_case(rng, n, m):
    hi = np.sort(rng.integers(0, 8, n, dtype=np.uint64).astype(np.uint32))
    lo = rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32)
    # sort lexicographically by (hi, lo)
    order = np.lexsort((lo, hi))
    hi, lo = hi[order], lo[order]
    qh = rng.choice(np.concatenate([hi, [np.uint32(3)]]), size=m)
    ql = rng.choice(np.concatenate([lo, [np.uint32(9)]]), size=m)
    return tuple(x.astype(np.uint32) for x in (hi, lo, qh, ql))


# -- NumPy oracles ------------------------------------------------------------


def _np_run_sum(flags, cols):
    """Run totals at run starts, 0 elsewhere; rows before the first run start
    belong to a run with no start, so they emit nothing."""
    out = [np.zeros_like(c) for c in cols]
    starts = np.flatnonzero(flags)
    for s, e in zip(starts, [*starts[1:], len(flags)]):
        for o, c in zip(out, cols):
            o[s] = c[s:e].sum(dtype=c.dtype)  # wraps as the device's add does
    return out


def _np_pack(hi, lo):
    return (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)


def _np_bucket_rank(key_s):
    out = np.zeros(len(key_s), dtype=np.int32)
    for i in range(1, len(key_s)):
        out[i] = out[i - 1] + 1 if key_s[i] == key_s[i - 1] else 0
    return out


# -- one checker per primitive: (rng, n) ---------------------------------------


def _check_run_sum(rng, n):
    for _ in range(3):
        flags, cols = _run_sum_case(rng, n)
        got = run_sum(jnp.asarray(flags), tuple(jnp.asarray(c) for c in cols))
        for g, w in zip(got, _np_run_sum(flags, cols)):
            _identical(g, w)


def _check_multi_take(rng, n):
    # gathers from a zero-length source are undefined (real batches have pow2
    # caps >= 8); n == 0 pairs with m == 0
    for m in (0, 1, n, 2 * n + 1) if n else (0,):
        cols, idx = _multi_take_case(rng, n, m)
        got = multi_take(tuple(jnp.asarray(c) for c in cols), jnp.asarray(idx))
        assert len(got) == len(cols)
        for g, c in zip(got, cols):
            _identical(g, c[idx])


def _check_searchsorted(rng, n):
    for m in (0, 1, 7, 65):
        a, q = _probe_case(rng, n, m)
        for side in ("left", "right"):
            got = searchsorted(jnp.asarray(a), jnp.asarray(q), side)
            _identical(got, np.searchsorted(a, q, side=side).astype(np.int32))


def _check_searchsorted2(rng, n):
    for m in (1, 7, 65):
        hi, lo, qh, ql = _probe2_case(rng, n, m)
        for side in ("left", "right"):
            got = searchsorted2(*(jnp.asarray(x) for x in (hi, lo, qh, ql)), side)
            want = np.searchsorted(_np_pack(hi, lo), _np_pack(qh, ql), side=side)
            _identical(got, want.astype(np.int32))


def _check_route_dest(rng, n):
    h = rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32)
    if n > 1:
        h[-1] = np.uint32(0xFFFFFFFF)  # the pad sentinel routes like any hash
    for n_dest in (1, 2, 5, 8):
        got = route_dest(jnp.asarray(h), n_dest)
        _identical(got, (h.astype(np.uint64) % n_dest).astype(np.int32))


def _check_bucket_rank(rng, n):
    for n_keys in (1, 3, 9):
        key_s = np.sort(rng.integers(0, n_keys, n).astype(np.int32))
        _identical(bucket_rank(jnp.asarray(key_s)), _np_bucket_rank(key_s))


# (checker, seed, smallest defined n): a search over empty keys, and a rank
# within an empty vector, are undefined in the lowerings (capacities are >= 8)
PRIMITIVES = {
    "run_sum": (_check_run_sum, 11, 0),
    "multi_take": (_check_multi_take, 17, 0),
    "searchsorted": (_check_searchsorted, 23, 1),
    "searchsorted2": (_check_searchsorted2, 29, 1),
    "route_dest": (_check_route_dest, 31, 0),
    "bucket_rank": (_check_bucket_rank, 37, 1),
}


def _cases(sizes):
    return [
        pytest.param(name, n, id=f"{name}-{n}")
        for name, (_check, _seed, min_n) in PRIMITIVES.items()
        for n in sizes
        if n >= min_n
    ]


@pytest.mark.parametrize("name,n", _cases(TIER1_SIZES))
def test_primitive_matches_numpy(name, n):
    check, seed, _min_n = PRIMITIVES[name]
    check(np.random.default_rng(seed + n), n)


@pytest.mark.slow
@pytest.mark.kernelbench
@pytest.mark.parametrize("name,n", _cases(BENCH_SIZES))
def test_primitive_matches_numpy_at_capacity(name, n):
    """The same properties at realistic tick capacities."""
    check, seed, _min_n = PRIMITIVES[name]
    check(np.random.default_rng(seed + n), n)


def test_run_sum_float_columns():
    """Float columns sum in row order within a run, as the scatter-add does."""
    rng = np.random.default_rng(3)
    flags, cols = _run_sum_case(rng, 16)
    # halves and quarters: every partial sum is exact, whatever the order
    f = (rng.integers(-64, 64, 16) / 4).astype(np.float32)
    got = run_sum(jnp.asarray(flags), (*(jnp.asarray(c) for c in cols), jnp.asarray(f)))
    for g, w in zip(got, _np_run_sum(flags, (*cols, f))):
        _identical(g, w)


def test_multi_take_empty_cols():
    assert multi_take((), jnp.asarray([0, 1], dtype=jnp.int32)) == ()


def test_kernel_backend_is_an_unknown_parameter():
    """There is one implementation and nothing to select: SET answers as it
    does for any unknown name."""
    from materialize_tpu.adapter import Coordinator
    from materialize_tpu.sql.plan import PlanError

    c = Coordinator()
    with pytest.raises(PlanError, match="unknown configuration parameter: kernel_backend"):
        c.execute("ALTER SYSTEM SET kernel_backend = 'xla'")
    assert "kernel_backend" not in c.configs.names()
