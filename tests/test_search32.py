"""Branchless fixed-depth binary search (ops/search.py) vs NumPy oracles.

The probe kernels replaced `jnp.searchsorted` (a vmapped while loop) with
unrolled branchless binary search; these tests pin the exact searchsorted
contract — including duplicates, all-smaller/all-larger queries, and the
two-key (hi, lo) pair order — against np.searchsorted on the packed u64.
`merge_perm` (the merge order from the short side's ranks alone) is pinned
element for element against the two-search construction it replaced, kept
here as the reference, and against a NumPy stable merge.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from materialize_tpu.ops.search import (
    merge_perm,
    searchsorted,
    searchsorted2,
    sort_perm,
)
from materialize_tpu.repr.batch import MIN_CAP


@pytest.mark.parametrize("n", [1, 2, 3, 8, 17, 64, 1000])
@pytest.mark.parametrize("side", ["left", "right"])
def test_searchsorted_matches_numpy(rng, n, side):
    a = np.sort(rng.integers(0, max(n // 2, 2), n).astype(np.uint32))
    q = rng.integers(-1, max(n // 2, 2) + 1, 257).astype(np.int64)
    q32 = q.clip(0, None).astype(np.uint32)
    got = np.asarray(searchsorted(a, q32, side=side))
    want = np.searchsorted(a, q32, side=side)
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.int32


@pytest.mark.parametrize("side", ["left", "right"])
def test_searchsorted_extremes(side):
    a = np.array([5, 5, 5, 5], dtype=np.uint32)
    q = np.array([0, 5, 9, 0xFFFFFFFF], dtype=np.uint32)
    got = np.asarray(searchsorted(a, q, side=side))
    np.testing.assert_array_equal(got, np.searchsorted(a, q, side=side))


@pytest.mark.parametrize("n", [1, 2, 8, 33, 256])
@pytest.mark.parametrize("side", ["left", "right"])
def test_searchsorted2_matches_packed_u64(rng, n, side):
    hi = rng.integers(0, 4, n).astype(np.uint32)
    lo = rng.integers(0, 4, n).astype(np.uint32)
    packed = (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)
    order = np.argsort(packed, kind="stable")
    hi, lo, packed = hi[order], lo[order], packed[order]
    qh = rng.integers(0, 5, 301).astype(np.uint32)
    ql = rng.integers(0, 5, 301).astype(np.uint32)
    qp = (qh.astype(np.uint64) << np.uint64(32)) | ql.astype(np.uint64)
    got = np.asarray(searchsorted2(hi, lo, qh, ql, side=side))
    np.testing.assert_array_equal(got, np.searchsorted(packed, qp, side=side))


def test_searchsorted2_sentinel_rows_sort_last(rng):
    # PAD rows carry the maximal hi key: probes below it must never land past
    # a pad boundary on the left side
    hi = np.array([1, 2, 0xFFFFFFFF, 0xFFFFFFFF], dtype=np.uint32)
    lo = np.array([9, 0, 0, 5], dtype=np.uint32)
    got = np.asarray(
        searchsorted2(
            hi,
            lo,
            np.array([0xFFFFFFFE], dtype=np.uint32),
            np.array([0xFFFFFFFF], dtype=np.uint32),
            side="right",
        )
    )
    np.testing.assert_array_equal(got, [2])


def test_sort_perm_matches_lexsort(rng):
    n = 500
    cols = (
        rng.integers(0, 5, n).astype(np.uint32),
        rng.integers(0, 5, n).astype(np.int32),
        rng.integers(0, 5, n).astype(np.uint32),
    )
    got = np.asarray(sort_perm(cols))
    want = np.lexsort(cols)
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.int32


def test_sort_perm_stable_bool():
    keys = np.array([True, False, True, False, False], dtype=np.bool_)
    got = np.asarray(sort_perm((keys,)))
    np.testing.assert_array_equal(got, np.lexsort((keys,)))


PAD = 0xFFFFFFFF  # repr/hashing.py's PAD_HASH: padding rows sort last


def two_search_merge_perm(a_hi, a_lo, b_hi, b_lo):
    """The construction `merge_consolidate` used through PR 32: every row of
    both sides binary-searched into the other, positions inverted by a
    scatter. Kept as the reference `merge_perm` must equal."""
    na, nb = a_hi.shape[0], b_hi.shape[0]
    pa = jnp.arange(na, dtype=jnp.int32) + searchsorted2(
        b_hi, b_lo, a_hi, a_lo, side="left"
    )
    pb = jnp.arange(nb, dtype=jnp.int32) + searchsorted2(
        a_hi, a_lo, b_hi, b_lo, side="right"
    )
    pos = jnp.concatenate([pa, pb])
    return (pos * 0).at[pos].set(jnp.arange(na + nb, dtype=jnp.int32))


def _sorted_side(rng, n, kind, live):
    """(hi, lo) sorted by pair: `live` rows drawn as `kind` says, PAD after."""
    if kind == "no_ties":  # hi unique across BOTH sides (a odd, b even: caller)
        hi = rng.permutation(4 * n)[:live].astype(np.uint32) * 2
        lo = rng.integers(0, 1 << 32, live, dtype=np.uint64).astype(np.uint32)
    elif kind == "every_key_equal":
        hi = np.full(live, 7, np.uint32)
        lo = np.full(live, 9, np.uint32)
    else:  # few distinct pairs: ties inside each side and across both
        hi = rng.integers(0, 5, live).astype(np.uint32)
        lo = rng.integers(0, 3, live).astype(np.uint32)
    order = np.lexsort((lo, hi))
    pad = np.full(n - live, PAD, np.uint32)
    return np.concatenate([hi[order], pad]), np.concatenate([lo[order], pad * 0])


MERGE_SHAPES = [(4096, 256), (256, 4096), (1024, 1024), (8, 8), (16 * MIN_CAP, MIN_CAP)]
MERGE_KINDS = [
    "no_ties",
    "every_key_equal",
    "equal_across_sides",
    "a_all_padding",
    "b_all_padding",
    "both_all_padding",
]


@pytest.mark.parametrize("kind", MERGE_KINDS)
@pytest.mark.parametrize("na,nb", MERGE_SHAPES)
def test_merge_perm_is_the_stable_merge(rng, na, nb, kind):
    live_a = 0 if kind in ("a_all_padding", "both_all_padding") else (3 * na) // 4
    live_b = 0 if kind in ("b_all_padding", "both_all_padding") else (3 * nb) // 4
    a_hi, a_lo = _sorted_side(rng, na, kind, live_a)
    b_hi, b_lo = _sorted_side(rng, nb, kind, live_b)
    if kind == "no_ties":
        b_hi = np.where(b_hi == PAD, b_hi, b_hi + 1)  # odd: never one of a's
    got = np.asarray(merge_perm(a_hi, a_lo, b_hi, b_lo))
    assert got.dtype == np.int32 and got.shape == (na + nb,)
    # (a) the two-search construction, element for element
    np.testing.assert_array_equal(
        got, np.asarray(two_search_merge_perm(*map(jnp.asarray, (a_hi, a_lo, b_hi, b_lo))))
    )
    # (b) NumPy's stable merge: by (hi, lo), then a's rows before b's
    side = np.concatenate([np.zeros(na, np.int8), np.ones(nb, np.int8)])
    want = np.lexsort(
        (side, np.concatenate([a_lo, b_lo]), np.concatenate([a_hi, b_hi]))
    )
    np.testing.assert_array_equal(got, want)
