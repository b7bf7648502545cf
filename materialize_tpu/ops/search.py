"""Branchless fixed-depth binary search + 32-bit sort plumbing.

`jnp.searchsorted` lowers to a vmapped `lax.while_loop` — one sequential,
data-dependent loop per probe kernel. The r5 CPU profile counted ~45 such
loops per Q3 tick, and on the TPU VPU data-dependent control flow defeats
vectorization entirely. Every probe in this engine searches an array whose
length is STATIC (pow2-bucketed capacities), so the loop is replaced by a
fixed-depth unrolled binary search: ceil(log2(n)) + 1 gather/compare/select
steps with no control flow at all — the accelerator-native scan formulation
(cf. arXiv:2505.15112) and the gather-structured probe shape of
hash-partitioned join hardware (cf. arXiv:1905.13376).

`searchsorted` is the single-key u32 search (join `_probe_ranges`, reduce
`lookup_accums`, output-slot owner searches); `searchsorted2` is the two-key
(hi, lo) pair search. Invariant per step: the insertion point lies in
[pos, pos + cur]; all positions i32.

`merge_perm` is the stable-merge permutation of two (hi, lo)-sorted sides
backing `merge_consolidate` / `merge_consolidate_accums`: one `searchsorted2`
of the SHORTER side into the longer, a scatter of that many marks and one
prefix sum. The longer side is never searched.

`sort_perm` is the 32-bit `jnp.lexsort`: under x64, jnp's argsort/lexsort
carry an i64 iota operand through the sort — a 64-bit operand the TPU splits
into u32 pairs. `sort_perm` threads explicit i32 iotas instead, so compiled
ticks contain no 64-bit sort operands at all.
"""

from __future__ import annotations

import jax.lax as lax
import jax.numpy as jnp


def _pred(a_elem: jnp.ndarray, q: jnp.ndarray, side: str) -> jnp.ndarray:
    return (a_elem < q) if side == "left" else (a_elem <= q)


def _pred2(a_hi, a_lo, q_hi, q_lo, side: str) -> jnp.ndarray:
    """(hi, lo) pair comparison: a < q (left) / a <= q (right) on the packed
    64-bit order, evaluated entirely in 32-bit lanes."""
    if side == "left":
        return (a_hi < q_hi) | ((a_hi == q_hi) & (a_lo < q_lo))
    return (a_hi < q_hi) | ((a_hi == q_hi) & (a_lo <= q_lo))


def searchsorted(a: jnp.ndarray, q: jnp.ndarray, side: str = "left") -> jnp.ndarray:
    """np.searchsorted over a sorted array of STATIC length, branchless.

    Returns i32 insertion points in [0, n]. ceil(log2(n)) + 1 unrolled
    steps; no data-dependent control flow (vectorizes on XLA:CPU and the
    TPU VPU alike).
    """
    n = int(a.shape[0])
    pos = jnp.zeros(q.shape, dtype=jnp.int32)
    cur = n
    while cur > 1:
        half = cur >> 1
        mid = pos + (half - 1)  # compare a[pos + half - 1]
        pos = jnp.where(_pred(a[mid], q, side), pos + half, pos)
        cur -= half
    return pos + _pred(a[pos], q, side).astype(jnp.int32)


def searchsorted2(
    a_hi: jnp.ndarray,
    a_lo: jnp.ndarray,
    q_hi: jnp.ndarray,
    q_lo: jnp.ndarray,
    side: str = "left",
) -> jnp.ndarray:
    """Two-key branchless searchsorted: `a` sorted by (hi, lo) pairs.

    The 32-bit replacement for searching a packed u64 key `(hi << 32) | lo`
    — same order, two u32 gathers per step instead of one split u64.
    """
    n = int(a_hi.shape[0])
    pos = jnp.zeros(q_hi.shape, dtype=jnp.int32)
    cur = n
    while cur > 1:
        half = cur >> 1
        mid = pos + (half - 1)
        go = _pred2(a_hi[mid], a_lo[mid], q_hi, q_lo, side)
        pos = jnp.where(go, pos + half, pos)
        cur -= half
    return pos + _pred2(a_hi[pos], a_lo[pos], q_hi, q_lo, side).astype(jnp.int32)


def merge_rank(
    a_hi: jnp.ndarray, a_lo: jnp.ndarray, b_hi: jnp.ndarray, b_lo: jnp.ndarray
) -> jnp.ndarray:
    """The shorter side's rows ranked into the longer (`b` when the sides
    are as long): per row, the rows of the other side the stable merge puts
    before it. `b`'s rows go after `a`'s equal ones."""
    if int(b_hi.shape[0]) <= int(a_hi.shape[0]):
        return searchsorted2(a_hi, a_lo, b_hi, b_lo, side="right")
    return searchsorted2(b_hi, b_lo, a_hi, a_lo, side="left")


def merge_perm(
    a_hi: jnp.ndarray,
    a_lo: jnp.ndarray,
    b_hi: jnp.ndarray,
    b_lo: jnp.ndarray,
    rank: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Gather permutation of the stable merge of two (hi, lo)-sorted sides.

    `concat(a, b)[perm]` is sorted by (hi, lo) with `a`'s rows before `b`'s
    among equal pairs; i32, length na + nb. Only the shorter side is ranked:
    its rows' output slots `j + #{rows of the other side before it}` are
    strictly increasing, the other side fills the remaining slots in its own
    order, so a 0/1 mark of those slots and its inclusive prefix sum `c` name
    every slot's source: the c-th row of the short side where marked, the
    (p - c)-th row of the long side elsewhere. A head merge (T, T/16) thus
    searches T/16 rows, not T + T/16. `rank` is `merge_rank`'s, where the
    caller has it already. (The mark's zeros derive from the data so varying
    manual axes match under shard_map.)
    """
    na, nb = int(a_hi.shape[0]), int(b_hi.shape[0])
    b_short = nb <= na
    if rank is None:
        rank = merge_rank(a_hi, a_lo, b_hi, b_lo)
    slot = lax.iota(jnp.int32, rank.shape[0]) + rank
    zeros = jnp.broadcast_to((a_hi[:1] * 0).astype(jnp.int32), (na + nb,))
    mark = zeros.at[slot].set(1, indices_are_sorted=True, unique_indices=True)
    c = jnp.cumsum(mark)
    p = lax.iota(jnp.int32, na + nb)
    # rows of `b` sit behind `a`'s na in the concatenation
    short_row, long_row = (na + c - 1, p - c) if b_short else (c - 1, na + p - c)
    return jnp.where(mark != 0, short_row, long_row)


def sort_perm(cols) -> jnp.ndarray:
    """`jnp.lexsort(cols)` with an i32 iota: last column is the primary key.

    Returns the i32 permutation that stably sorts by (cols[-1], …, cols[0]).
    Implemented as one least-significant-first pass per key column, each a
    `lax.sort` over (key, position) with the running permutation as payload:
    position as the second key makes the pass stable without the stable-sort
    expansion. That shape is for the TPU compiler, which emits a bitonic
    network per sort and whose compile time grows steeply with the operand
    and key count: ONE stable 3-key + iota sort cost 60-100 s of compile at
    any n >= 2^16, this chain about a quarter of that (chip compiler, PR 25;
    tests/test_chip_compile.py compiles it at 2^22). The permutation is the
    unique stable lexsort either way. No 64-bit operand enters a sort unless
    a key column is itself 64-bit.
    """
    cols = [
        c.astype(jnp.int8) if c.dtype == jnp.bool_ else c
        for c in (jnp.asarray(x) for x in cols)
    ]
    iota = lax.iota(jnp.int32, int(cols[0].shape[0]))
    perm = iota
    for i, key in enumerate(cols):  # least significant first
        k = key if i == 0 else key[perm]
        _, _, perm = lax.sort((k, iota, perm), num_keys=2, is_stable=False)
    return perm
