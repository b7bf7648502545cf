"""Batched incremental join kernels.

The TPU analogue of the reference's `mz_join_core` cursor merge
(src/compute/src/render/join/mz_join_core.rs:57): instead of a per-key cursor
walk, a probe batch joins an arrangement batch as a two-pass vectorized
program —

  pass 1 (count):       lo/hi = binary search of probe hashes in the sorted
                        arrangement hash column; match counts = hi - lo.
  host:                 read total, bucket the output capacity (pow2).
  pass 2 (materialize): output slot j maps back to (probe row, match offset)
                        by binary search over the running count prefix sum;
                        gather both sides, verify true key equality (hash
                        collisions annihilate via diff=0), emit
                        (vals_l ++ vals_r, max(t_l, t_r), d_l * d_r).

`max(t_l, t_r)` is the total-order least upper bound of the two update times,
exactly differential's product rule for join. Diff-multiplication makes
padding and collision rows inert without masks.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..repr.batch import PAD_TIME, UpdateBatch, bucket_cap
from ..repr.hashing import PAD_HASH
from .consolidate import compact_to
from .permute import multi_take
from .search import searchsorted


def _probe_ranges(probe: UpdateBatch, arr: UpdateBatch):
    # branchless fixed-depth binary search (ops/search.py): no while loop,
    # i32 positions — the probe kernel is pure gather/compare/select.
    lo = searchsorted(arr.hashes, probe.hashes, side="left")
    hi = searchsorted(arr.hashes, probe.hashes, side="right")
    counts = jnp.where(probe.live, hi - lo, 0)
    return lo, counts


@jax.jit
def _join_total(probe: UpdateBatch, arr: UpdateBatch) -> jnp.ndarray:
    _, counts = _probe_ranges(probe, arr)
    return jnp.sum(counts)


def join_total(probe: UpdateBatch, arr: UpdateBatch) -> jnp.ndarray:
    return _join_total(probe, arr)


def join_materialize(
    probe: UpdateBatch, arr: UpdateBatch, out_cap: int, swap: bool = False
) -> UpdateBatch:
    """Materialize probe ⋈ arr into a raw batch of capacity `out_cap`.

    Output vals are probe.vals ++ arr.vals, or arr.vals ++ probe.vals when
    `swap` (so the dataflow can keep a fixed left/right column order
    regardless of which side streamed). Requires out_cap >= total matches
    (host checks via `join_total`).
    """
    # forwards only: the harness wraps this un-jitted name and reads the
    # device program `jit__join_materialize` (chipbench/metrics/)
    return _join_materialize(probe, arr, out_cap, swap)


@partial(jax.jit, static_argnames=("out_cap", "swap"))
def _join_materialize(
    probe: UpdateBatch, arr: UpdateBatch, out_cap: int, swap: bool
) -> UpdateBatch:
    lo, counts = _probe_ranges(probe, arr)
    cum = jnp.cumsum(counts)  # inclusive, i32 (counts bounded by capacities)
    total = cum[-1] if counts.shape[0] > 0 else jnp.zeros((), dtype=jnp.int32)

    j = jnp.arange(out_cap, dtype=cum.dtype)
    # probe row owning output slot j: first i with cum[i] > j
    pi = searchsorted(cum, j, side="right")
    pi = jnp.minimum(pi, probe.cap - 1)
    prev = jnp.where(pi > 0, cum[pi - 1], 0)
    ai = lo[pi] + (j - prev)
    ai = jnp.clip(ai, 0, arr.cap - 1)
    valid = j < total

    # fused multi-column gather: one dtype-grouped pass per side instead of
    # one XLA gather per key/val/time/diff column
    nkp = len(probe.keys)
    p_g = multi_take(
        (*probe.keys, *probe.vals, probe.hashes, probe.times, probe.diffs), pi
    )
    a_g = multi_take(
        (*arr.keys, *arr.vals, arr.times, arr.diffs), ai
    )

    # true key equality (collision guard); canonical views so float NULL
    # sentinels (NaN) compare equal and -0.0 == 0.0 (value_view is
    # elementwise, so it commutes with the gather)
    from ..repr.hashing import value_view

    eq = jnp.ones((out_cap,), dtype=jnp.bool_)
    for pk, ak in zip(p_g[:nkp], a_g[: len(arr.keys)]):
        eq = eq & (value_view(pk) == value_view(ak))

    diffs = jnp.where(valid & eq, p_g[-1] * a_g[-1], 0)
    times = jnp.maximum(p_g[-2], a_g[-2])
    ok = valid & eq & (diffs != 0)
    left = tuple(p_g[nkp : nkp + len(probe.vals)])
    right = tuple(a_g[len(arr.keys) : len(arr.keys) + len(arr.vals)])
    vals = (right + left) if swap else (left + right)
    return UpdateBatch(
        hashes=jnp.where(ok, p_g[-3], PAD_HASH),
        keys=(),
        vals=vals,
        times=jnp.where(ok, times, PAD_TIME),
        diffs=diffs,
    )


def join_against(
    probe: UpdateBatch, batches: list[UpdateBatch], swap: bool = False, floor: int = 0
):
    """Join a probe batch against every batch of an arrangement (host driver).

    Returns a list of raw output batches (possibly empty). Sizes outputs by a
    count pass per spine batch; capacities are pow2-bucketed to bound
    recompilation. Where all of the probe's matches together fit `floor` rows
    they come back as ONE batch of that capacity, through the same programs
    whichever batches hold them: counts that small split over the batches, and
    cross their small buckets, by chance from tick to tick, and every program
    downstream would follow them.
    """
    totals = [int(join_total(probe, arr)) for arr in batches]
    if 0 < sum(totals) <= floor:
        acc = None
        for arr in batches:  # also one that matched nothing: its turn comes
            out = join_materialize(probe, arr, floor, swap)
            acc = out if acc is None else UpdateBatch.concat(acc, out)
        return [acc if len(batches) == 1 else compact_to(acc, floor)[0]]
    return [
        join_materialize(probe, arr, bucket_cap(t), swap)
        for arr, t in zip(batches, totals)
        if t
    ]
