"""Native C++ host kernels vs NumPy fallback."""

import numpy as np
import pytest

from materialize_tpu.utils.native import (
    _consolidate_numpy,
    advance_times_host,
    consolidate_host,
    get_native,
)


def mkcols(rng, n, ncols=2, dtype=np.int64):
    cols = {f"c{i}": rng.integers(0, 10, n).astype(dtype) for i in range(ncols)}
    cols["times"] = rng.integers(0, 4, n).astype(np.uint64)
    cols["diffs"] = rng.integers(-2, 3, n).astype(np.int64)
    return cols


def canon(cols):
    out = {}
    keys = sorted(k for k in cols if k not in ("times", "diffs"))
    for i in range(len(cols["times"])):
        key = tuple(int(cols[k][i]) for k in keys) + (int(cols["times"][i]),)
        out[key] = out.get(key, 0) + int(cols["diffs"][i])
    return {k: v for k, v in out.items() if v != 0}


def test_native_builds():
    assert get_native() is not None, "g++ native kernel should build in this image"


def test_native_matches_numpy(rng):
    for n in (1, 7, 100, 5000):
        cols = mkcols(rng, n)
        got = consolidate_host({k: v.copy() for k, v in cols.items()})
        keys = sorted(k for k in cols if k not in ("times", "diffs"))
        want = _consolidate_numpy({k: v.copy() for k, v in cols.items()}, keys)
        assert canon(got) == canon(want) == canon(cols)


def test_non64_falls_back(rng):
    cols = mkcols(rng, 50, dtype=np.int32)
    got = consolidate_host({k: v.copy() for k, v in cols.items()})
    assert canon(got) == canon(cols)
    assert got["c0"].dtype == np.int32


def test_advance_times():
    t = np.array([0, 5, 10], dtype=np.uint64)
    out = advance_times_host(t, 5)
    assert out.tolist() == [5, 5, 10]


def test_native_is_the_path_taken(rng, monkeypatch):
    """200k rows go through the native kernel (the NumPy fallback is not
    entered) and come out as the fallback's rows, bit for bit."""
    from materialize_tpu.utils import native

    if get_native() is None:
        pytest.skip("no compiler")
    cols = mkcols(rng, 200_000, ncols=3)
    keys = sorted(k for k in cols if k not in ("times", "diffs"))
    want = _consolidate_numpy({k: v.copy() for k, v in cols.items()}, keys)

    def no_fallback(*_a, **_k):
        raise AssertionError("consolidate_host fell back to NumPy")

    monkeypatch.setattr(native, "_consolidate_numpy", no_fallback)
    got = consolidate_host(cols)
    assert sorted(got) == sorted(want)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])
