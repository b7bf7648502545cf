"""repr layer: hashing determinism, batch build/pad/roundtrip, antichains."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from materialize_tpu.obs.metrics import REGISTRY
from materialize_tpu.repr import (
    MAX_DEVICE_TIME,
    Antichain,
    ColType,
    PAD_HASH,
    RelationDesc,
    StringDictionary,
    UpdateBatch,
    bucket_cap,
    hash_columns,
    hash_columns_np,
)


def test_hash_deterministic_and_uniformish():
    a = np.arange(1000, dtype=np.int64)
    h1 = hash_columns_np((a,))
    h2 = hash_columns_np((a,))
    np.testing.assert_array_equal(h1, h2)
    assert len(np.unique(h1)) == 1000
    assert (h1 != PAD_HASH).all()
    # multi-column hash differs from single-column
    h3 = hash_columns_np((a, a))
    assert (h1 != h3).any()


def test_hash_order_sensitive():
    a = np.array([1, 2], dtype=np.int64)
    b = np.array([2, 1], dtype=np.int64)
    assert (hash_columns_np((a, b)) != hash_columns_np((b, a))).all()


def test_bucket_cap():
    assert bucket_cap(0) == 8
    assert bucket_cap(8) == 8
    assert bucket_cap(9) == 16
    assert bucket_cap(1000) == 1024


def test_batch_build_roundtrip():
    cols = (
        np.array([3, 1, 2], dtype=np.int64),
        np.array([30, 10, 20], dtype=np.int64),
    )
    b = UpdateBatch.build((), cols, np.array([5, 5, 5]), np.array([1, 1, -1]))
    assert b.cap == 8  # bucketed
    assert int(b.count()) == 3
    rows = b.to_rows()
    assert ((1, 10), 5, 1) in rows
    assert ((2, 20), 5, -1) in rows
    assert len(rows) == 3


def test_batch_capacity_growth():
    b = UpdateBatch.build((), (np.arange(3, dtype=np.int64),), [0, 0, 0], [1, 1, 1])
    big = b.with_capacity(32)
    assert big.cap == 32
    assert int(big.count()) == 3


# -- the host build against the device build (ISSUE 31) ----------------------

_F32 = np.array([1.5, np.nan, -0.0, 0.0, -np.nan, np.inf, 1e-40, -3.25], dtype=np.float32)
_F64 = np.array([1e300, np.nan, -0.0, 2.5, -1e-300, 1e-40, -np.inf, 7.0])
COLUMNS = {
    "int64": lambda n: (np.arange(n, dtype=np.int64) - 3) * 0x1_0000_0001,
    "int32": lambda n: (np.arange(n, dtype=np.int32) - 3) * 65_537,
    "uint64": lambda n: np.arange(n, dtype=np.uint64) + np.uint64(0xFFFFFFFFFFFFFFF0),
    "float32": lambda n: np.resize(_F32, n),
    "float64": lambda n: np.resize(_F64, n),
    "bool": lambda n: np.arange(n) % 3 == 0,
    "int_list": lambda n: [7 * i - 9 for i in range(n)],
    "float_list": lambda n: [i / 7 for i in range(n)],
}
TIMES = {
    "uint64_sentinel": lambda n: np.resize(
        np.array([0, 5, 0xFFFFFFFFFFFFFFFF, MAX_DEVICE_TIME, 0xFFFFFFFF, 1 << 40], dtype=np.uint64), n
    ),
    "int64_negative": lambda n: np.resize(np.array([-4, 3, 1 << 33, 0, MAX_DEVICE_TIME + 1], dtype=np.int64), n),
    "uint32": lambda n: np.resize(np.array([9, 0, 0xFFFFFFFF, 0xFFFFFFFE], dtype=np.uint32), n),
    "int_list": lambda n: [3] * n,
    "float64": lambda n: np.full(n, 2.0),
}
KEYS = {"keyless": 0, "one_key": 1, "two_keys": 2}
ROWS = ("0", "1", "cap-1", "cap")
CAPS = (None, 32)

# every column dtype under every keying, row count and capacity rule, and
# every kind of time column under every row count and capacity rule
BUILD_CASES = [
    (col, "int_list", keys, rows, cap) for col, keys, rows, cap in itertools.product(COLUMNS, KEYS, ROWS, CAPS)
] + [("int64", times, "keyless", rows, cap) for times, rows, cap in itertools.product(TIMES, ROWS, CAPS) if times != "int_list"]


def _on_device(x):
    return jnp.asarray(x)


def _bits(batch: UpdateBatch) -> list:
    return [(a.shape, a.dtype, np.asarray(a).tobytes()) for a in jax.tree_util.tree_leaves(batch)]


def _builds() -> tuple:
    fam = next(f for f in REGISTRY.families() if f.name == "mzt_batch_build_total")
    by_path = {dict(labels)["path"]: v for labels, v in fam.samples}
    return by_path.get("host", 0), by_path.get("device", 0)


@pytest.mark.parametrize("col, times, keys, rows, cap", BUILD_CASES, ids=["-".join(map(str, c)) for c in BUILD_CASES])
def test_host_build_equals_device_build(col, times, keys, rows, cap):
    """`build` over host columns (NumPy at capacity, one transfer) gives the
    pytree that `build` over the same data as `jax.Array`s gives: structure,
    shape, dtype and every bit, padding included."""
    width = cap or 16  # cap=None: 15 and 16 rows both bucket to 16
    n = {"0": 0, "1": 1, "cap-1": width - 1, "cap": width}[rows]
    vals = (COLUMNS[col](n), COLUMNS["int64"](n))
    key_cols = vals[: KEYS[keys]]
    t, d = TIMES[times](n), [(-1) ** i * (i + 1) for i in range(n)]
    host0, device0 = _builds()
    host = UpdateBatch.build(key_cols, vals, t, d, cap=cap)
    assert _builds() == (host0 + 1, device0)
    device = UpdateBatch.build(
        tuple(map(_on_device, key_cols)), tuple(map(_on_device, vals)), _on_device(t), _on_device(d), cap=cap
    )
    assert _builds() == (host0 + 1, device0 + 1)
    assert host.cap == (cap or bucket_cap(n)) and int(host.count()) == n
    assert jax.tree_util.tree_structure(host) == jax.tree_util.tree_structure(device)
    assert _bits(host) == _bits(device)
    for a, b in zip(jax.tree_util.tree_leaves(host), jax.tree_util.tree_leaves(device)):
        # where the arrays live is what the renders and the benchmark's
        # `state_arrays_off_device` see: as `jnp.asarray` left them
        assert isinstance(a, jax.Array) and a.committed == b.committed and a.devices() == b.devices()


def test_host_build_never_aliases_the_callers_arrays():
    """Also when the rows fill the capacity exactly: a zero-copy transfer (the
    CPU backend's) of the caller's own buffer would change under the batch."""
    vals, t, d = np.arange(8, dtype=np.int64), np.full(8, 3, dtype=np.uint32), np.ones(8, dtype=np.int64)
    b = UpdateBatch.build((), (vals,), t, d)
    want = _bits(b)
    vals += 100
    t += 1
    d -= 5
    assert b.cap == 8 and _bits(b) == want


def test_a_column_of_another_length_is_refused():
    with pytest.raises(ValueError, match="batch of 3 rows"):
        UpdateBatch.build((), (np.arange(1),), [0, 0, 0], [1, 1, 1])


@pytest.mark.parametrize("second", [None, *COLUMNS])
@pytest.mark.parametrize("first", list(COLUMNS))
def test_hash_columns_np_equals_hash_columns(first, second):
    cols = tuple(COLUMNS[c](64) for c in (first, second) if c)
    got = hash_columns_np(cols)
    want = np.asarray(hash_columns(tuple(map(_on_device, cols))))
    assert isinstance(got, np.ndarray) and got.dtype == want.dtype == np.uint32
    np.testing.assert_array_equal(got, want)


def test_relation_desc():
    d = RelationDesc.of(("id", ColType.INT64), ("name", ColType.STRING), key=(0,))
    assert d.arity == 2
    assert d.index_of("name") == 1
    assert d.dtypes[0] == np.dtype(np.int64)


def test_string_dictionary():
    sd = StringDictionary()
    codes = sd.encode_many(["a", "b", "a"])
    np.testing.assert_array_equal(codes, [0, 1, 0])
    assert sd.decode_many(codes) == ["a", "b", "a"]
    assert sd.lookup("zzz") is None


def test_antichain_total_order():
    f = Antichain.from_elem(5)
    assert f.less_equal(5) and f.less_equal(9)
    assert not f.less_equal(4)
    assert not f.less_than(5)
    assert Antichain.empty().is_empty()
    assert f.meet(Antichain.from_elem(3)).frontier() == 3
    assert f.join(Antichain.from_elem(3)).frontier() == 5
    assert f.join(Antichain.empty()).is_empty()
