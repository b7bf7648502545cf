"""TopK / MIN / MAX: affected-group recompute via segmented sort + rank window.

The TPU analogue of the reference's hierarchical top_k and min/max reductions
(src/compute/src/render/top_k.rs:61, render/reduce.rs Hierarchical). Where the
reference bounds per-update cost with a 16-ary tower of thinning stages
(doc/developer/arrangements.md:100-135), the TPU design exploits batch
parallelism instead: a tick touches many groups at once, so we gather the
*full contents of every affected group* from the input arrangement (two-pass
sized vectorized binary-search gather), rank rows per group with one
segmented sort, and window by [offset, offset+k) over a segmented running sum
of multiplicities — no per-row expansion of diffs. Output deltas are emitted
self-correctingly: new_topk − old_topk, computed against the arrangement
before and after inserting the tick's delta.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..repr.batch import (
    DIFF_DTYPE,
    PAD_TIME,
    TIME_DTYPE,
    UpdateBatch,
    bucket_cap,
    to_device_time,
)
from ..repr.hashing import PAD_HASH
from .consolidate import advance_times, consolidate, gather_parts, row_equal_prev
from .permute import batch_permute, multi_take
from .search import searchsorted, sort_perm


@dataclass(frozen=True)
class TopKPlan:
    """Mirrors the reference's TopKPlan (src/compute-types/src/plan/top_k.rs:28).

    order_by: tuple of (val column index, descending) pairs.
    limit None = no limit (offset-only); k is required for the kernel path.
    nulls_last: per-order-column NULL placement; None = the pg default
    (NULLS LAST ascending, NULLS FIRST descending). MIN/MAX lowering sets
    all-True so NULL inputs never win a group (SQL aggregates ignore NULLs)
    while all-NULL groups still yield a NULL row.
    """

    group_cols: tuple[int, ...]
    order_by: tuple[tuple[int, bool], ...]
    limit: int | None
    offset: int = 0
    nulls_last: tuple[bool, ...] | None = None


def distinct_keys(delta_keyed: UpdateBatch) -> UpdateBatch:
    """Distinct (hash, key) probes of a keyed batch: one live row per key.

    Diffs are replaced by 1 (presence marker); vals dropped.
    """
    return _distinct_keys(delta_keyed)


@jax.jit
def _distinct_keys(delta_keyed: UpdateBatch) -> UpdateBatch:
    b = delta_keyed
    cols = [*(k for k in reversed(b.keys)), b.hashes]
    order = sort_perm(cols)
    g = multi_take((b.hashes, *b.keys, b.live), order)
    h, ks, live_in = g[0], tuple(g[1:-1]), g[-1]
    same = row_equal_prev((h, *ks))
    # first live row of each (hash,key) run survives; a run may mix live and
    # dead rows, so mark a row live if it's the first live one in its run
    seg = jnp.cumsum((~same).astype(jnp.int32)) - 1
    first_live = (
        jax.ops.segment_min(
            jnp.where(live_in, jnp.arange(h.shape[0]), h.shape[0]),
            seg,
            num_segments=h.shape[0],
        )[seg]
        == jnp.arange(h.shape[0])
    ) & live_in
    hashes = jnp.where(first_live, h, PAD_HASH)
    keys = tuple(jnp.where(first_live, k, jnp.zeros_like(k)) for k in ks)
    perm = sort_perm((~first_live,))
    g = multi_take(
        (
            hashes,
            *keys,
            jnp.where(first_live, 0, PAD_TIME).astype(TIME_DTYPE),
            jnp.where(first_live, 1, 0).astype(DIFF_DTYPE),
        ),
        perm,
    )
    return UpdateBatch(g[0], tuple(g[1:-2]), (), g[-2], g[-1])


def _gather_total(probes: UpdateBatch, arr: UpdateBatch) -> jnp.ndarray:
    return _gather_total_jit(probes, arr)


@jax.jit
def _gather_total_jit(probes: UpdateBatch, arr: UpdateBatch):
    lo = searchsorted(arr.hashes, probes.hashes, side="left")
    hi = searchsorted(arr.hashes, probes.hashes, side="right")
    return jnp.sum(jnp.where(probes.live, hi - lo, 0))


def _gather_materialize(probes: UpdateBatch, arr: UpdateBatch, out_cap: int) -> UpdateBatch:
    """All arrangement rows whose key matches a probe key (collision-checked)."""
    return _gather_materialize_jit(probes, arr, out_cap)


@partial(jax.jit, static_argnames=("out_cap",))
def _gather_materialize_jit(
    probes: UpdateBatch, arr: UpdateBatch, out_cap: int
) -> UpdateBatch:
    lo = searchsorted(arr.hashes, probes.hashes, side="left")
    hi = searchsorted(arr.hashes, probes.hashes, side="right")
    counts = jnp.where(probes.live, hi - lo, 0)
    cum = jnp.cumsum(counts)
    total = cum[-1]
    j = jnp.arange(out_cap, dtype=cum.dtype)
    pi = jnp.minimum(searchsorted(cum, j, side="right"), probes.cap - 1)
    prev = jnp.where(pi > 0, cum[pi - 1], 0)
    ai = jnp.clip(lo[pi] + (j - prev), 0, arr.cap - 1)
    valid = j < total
    from ..repr.hashing import value_view

    # one fused dtype-grouped gather for the whole arrangement payload
    a_row = batch_permute(arr, ai)
    p_keys = multi_take(probes.keys, pi)
    eq = jnp.ones((out_cap,), dtype=jnp.bool_)
    for pk, ak in zip(p_keys, a_row.keys):
        eq = eq & (value_view(pk) == value_view(ak))
    ok = valid & eq & (a_row.diffs != 0)
    return UpdateBatch(
        hashes=jnp.where(ok, a_row.hashes, PAD_HASH),
        keys=tuple(jnp.where(ok, k, 0) for k in a_row.keys),
        vals=tuple(jnp.where(ok, v, 0) for v in a_row.vals),
        times=jnp.where(ok, a_row.times, PAD_TIME),
        diffs=jnp.where(ok, a_row.diffs, 0),
    )


def gather_groups(
    probes: UpdateBatch, batches: list[UpdateBatch], as_of: int, val_dtypes=(),
    totals: list | None = None, floor: int = 0,
) -> UpdateBatch:
    """Current contents (as of `as_of`) of every probed group, consolidated.
    Where given, `totals` receives each batch's count of matching rows, which
    the host reads anyway to size the gather. Where they all fit `floor`
    rows, none included, every batch is gathered at that size and the parts
    compacted back to it (`gather_parts`): the shapes then follow neither
    the group's size nor which batches hold it nor whether the step had a
    group to gather."""
    counts = [int(_gather_total(probes, arr)) for arr in batches]
    if totals is not None:
        totals.extend(counts)
    parts = gather_parts(lambda i, cap: _gather_materialize(probes, batches[i], cap), counts, floor)
    if not parts:
        dtypes_k = tuple(k.dtype for k in probes.keys)
        return UpdateBatch.empty(8, dtypes_k, val_dtypes)
    acc = parts[0]
    for p in parts[1:]:
        acc = UpdateBatch.concat(acc, p)
    return consolidate(advance_times(acc, as_of))


def topk_select(
    rows: UpdateBatch, order_by, limit, offset: int, time, nulls_last=None
) -> UpdateBatch:
    """Window [offset, offset+limit) of each group's multiset, by order_by.

    rows: consolidated group contents (keys = group cols). Multiplicities are
    windowed with a segmented running sum — a row with diff 3 straddling the
    boundary keeps the in-window portion of its diff. `nulls_last` per order
    column; None = pg default (last when ascending, first when descending).
    """
    return _topk_select(rows, order_by, limit, offset, time, nulls_last)


@partial(jax.jit, static_argnames=("order_by", "limit", "offset", "nulls_last"))
def _topk_select(
    rows: UpdateBatch, order_by, limit, offset: int, time, nulls_last
) -> UpdateBatch:
    n = rows.cap
    d = jnp.maximum(rows.diffs, 0) * rows.live  # negative multiplicities ignored
    if nulls_last is None:
        nulls_last = tuple(not desc for _c, desc in order_by)
    sort_cols: list = []
    # tie-break: remaining val columns ascending for determinism
    used = [c for c, _ in order_by]
    for i in reversed(range(len(rows.vals))):
        if i not in used:
            sort_cols.append(_ord_view(rows.vals[i], False, True))
    for (c, desc), nl in zip(reversed(order_by), reversed(nulls_last)):
        sort_cols.append(_ord_view(rows.vals[c], desc, nl))
    for k in reversed(rows.keys):
        sort_cols.append(k)
    sort_cols.append(rows.hashes)
    order = sort_perm(sort_cols)
    b = batch_permute(rows, order)
    d = d[order]

    run_start = ~row_equal_prev((b.hashes, *b.keys))
    cum_incl = jnp.cumsum(d)
    idx = jnp.arange(n)
    first_idx = jax.lax.cummax(jnp.where(run_start, idx, -1))
    cum_before = (cum_incl - d) - (cum_incl - d)[first_idx]

    lim = (1 << 62) if limit is None else limit
    hi_ = jnp.minimum(cum_before + d, offset + lim)
    lo_ = jnp.maximum(cum_before, offset)
    out_d = jnp.maximum(hi_ - lo_, 0).astype(DIFF_DTYPE)
    ok = (out_d > 0) & b.live
    t = to_device_time(time)
    # raw output: the full row lives in vals; keys were only for grouping
    return UpdateBatch(
        hashes=jnp.where(ok, b.hashes, PAD_HASH),
        keys=(),
        vals=b.vals,
        times=jnp.where(ok, t, PAD_TIME),
        diffs=jnp.where(ok, out_d, 0),
    )


def _ord_view(col: jnp.ndarray, desc: bool, nulls_last: bool) -> jnp.ndarray:
    """Sortable view honoring direction and NULL placement.

    NULL sentinels (NaN / INT_MIN / -128) are mapped to the view's extreme so
    they land where `nulls_last` says regardless of direction. A real value
    equal to the extreme ties with NULL in ordering only (equality elsewhere
    is exact) — the documented in-band-sentinel edge.
    """
    from ..expr.scalar import derived_null

    c = col.astype(jnp.int8) if col.dtype == jnp.bool_ else col
    null = derived_null(c)
    if jnp.issubdtype(c.dtype, jnp.floating):
        view = -c if desc else c
        ext = jnp.float32(np.inf) if nulls_last else jnp.float32(-np.inf)
        return jnp.where(null, ext, view)
    # Bitwise NOT reverses the total order for both signed (two's complement:
    # ~x = -x-1, monotone decreasing, no INT_MIN overflow) and unsigned ints
    # (negation would wrap 0 to 0 and keep it minimal).
    view = ~c if desc else c
    info = jnp.iinfo(c.dtype)
    ext = jnp.asarray(info.max if nulls_last else info.min, c.dtype)
    return jnp.where(null, ext, view)


@jax.jit
def negate(b: UpdateBatch) -> UpdateBatch:
    return UpdateBatch(b.hashes, b.keys, b.vals, b.times, -b.diffs)


def topk_step(
    arrangement,
    delta_keyed: UpdateBatch,
    plan: TopKPlan,
    time: int,
    totals: list | None = None,
) -> UpdateBatch:
    """One tick of TopK: emits new_topk − old_topk for affected groups.

    `arrangement` is the input Arrangement keyed by plan.group_cols; the delta
    must already be keyed the same way. This function inserts the delta.
    `totals`, where given, receives the rows both gathers matched (per batch).
    A group of up to `offset + limit` rows is gathered at that row count's
    bucket, whatever its size from tick to tick.
    """
    probes = distinct_keys(delta_keyed)
    vdt = tuple(v.dtype for v in delta_keyed.vals)
    floor = 0 if plan.limit is None else bucket_cap(plan.offset + plan.limit)
    old_rows = gather_groups(probes, arrangement.batches, time, vdt, totals, floor)
    arrangement.insert(delta_keyed, already_keyed=True)
    new_rows = gather_groups(probes, arrangement.batches, time, vdt, totals, floor)
    old_top = topk_select(
        old_rows, plan.order_by, plan.limit, plan.offset, time, plan.nulls_last
    )
    new_top = topk_select(
        new_rows, plan.order_by, plan.limit, plan.offset, time, plan.nulls_last
    )
    out = UpdateBatch.concat(new_top, negate(old_top))
    return consolidate(out)
