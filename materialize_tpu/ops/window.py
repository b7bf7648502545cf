"""Window functions: affected-partition recompute, fully vectorized on device.

The TPU analogue of the reference's window-function strategy: the reference
evaluates window functions as `AggregateFunc` variants inside a reduce that
recomputes the whole group on any change (src/expr/src/relation/func.rs:1963
RowNumber/Rank/DenseRank/LagLead, src/sql/src/plan/query.rs window planning).
Here the same affected-group-recompute shape runs as batch kernels, reusing
the TopK chassis (ops/topk.py): a tick gathers the full contents of every
touched partition from the input arrangement, sorts them once with one
segmented lexsort, and computes every window function with segmented
prefix-sums — then emits new_output − old_output self-correctingly.

Multiplicities: row_number/lag/lead/ntile assign distinct values to duplicate
row instances, so consolidated rows with diff d are expanded into d
instances via the same two-pass sized searchsorted-gather used by group
gathers. rank/dense_rank/first_value/last_value and running aggregates are
computed per consolidated row and broadcast to instances.

Frames follow PostgreSQL defaults: with ORDER BY the frame is RANGE BETWEEN
UNBOUNDED PRECEDING AND CURRENT ROW (running aggregates include every peer
of the current row); without ORDER BY every partition row is a peer, so
aggregates cover the whole partition.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..repr.batch import DIFF_DTYPE, I64_DTYPE, PAD_TIME, UpdateBatch, bucket_cap, to_device_time
from ..repr.hashing import PAD_HASH, value_view
from .consolidate import row_equal_prev
from .permute import batch_permute
from .search import searchsorted, sort_perm
from .topk import _ord_view, distinct_keys, gather_groups, negate


@dataclass(frozen=True)
class WindowFuncSpec:
    """One window function column.

    func: row_number | rank | dense_rank | ntile | lag | lead | first_value |
          last_value | sum | count | min | max
    arg: val-column index of the argument (None for row_number/rank/
         dense_rank/count(*); the ntile bucket count rides in `offset`).
    offset: lag/lead distance (default 1) or ntile bucket count.
    out_dtype: numpy dtype name of the output column.
    """

    func: str
    arg: int | None = None
    offset: int = 1
    out_dtype: str = "int64"


@dataclass(frozen=True)
class WindowPlan:
    partition_cols: tuple  # val-column indices
    order_by: tuple  # ((val col, desc), ...)
    funcs: tuple  # of WindowFuncSpec
    nulls_last: tuple | None = None  # per-order-col; None = pg default


def _derived_null(col: jnp.ndarray) -> jnp.ndarray:
    from ..expr.scalar import derived_null

    c = col.astype(jnp.int8) if col.dtype == jnp.bool_ else col
    return derived_null(c)


def _null_sentinel_arr(dtype) -> jnp.ndarray:
    from ..expr.scalar import null_sentinel

    dt = np.dtype(dtype)
    if dt == np.bool_:
        dt = np.dtype(np.int8)
    return jnp.asarray(null_sentinel(dt), dtype=dt)


def _seg_scan_min(view: jnp.ndarray, reset: jnp.ndarray, take_max: bool):
    """Segmented running min (or max) of `view`, resetting where `reset`."""

    def comb(a, b):
        va, _ra = a
        vb, rb = b
        keep = jnp.where(take_max, jnp.maximum(va, vb), jnp.minimum(va, vb))
        return (jnp.where(rb, vb, keep), a[1] | rb)

    out, _ = jax.lax.associative_scan(comb, (view, reset))
    return out


def window_compute(rows: UpdateBatch, plan: WindowPlan, time, out_cap: int) -> UpdateBatch:
    """All window outputs for the partitions present in `rows`.

    rows: consolidated partition contents (keys = partition cols, vals = the
    full row). Output: one instance per unit of multiplicity, vals = original
    row columns ++ one column per plan.funcs entry, every diff = 1.
    """
    return _window_compute(rows, plan, time, out_cap)


@partial(jax.jit, static_argnames=("plan", "out_cap"))
def _window_compute(
    rows: UpdateBatch, plan: WindowPlan, time, out_cap: int
) -> UpdateBatch:
    n = rows.cap
    # -- one segmented sort of the consolidated rows ------------------------
    nl_tup = plan.nulls_last
    if nl_tup is None:
        nl_tup = tuple(not desc for _c, desc in plan.order_by)
    sort_cols: list = []
    used = [c for c, _ in plan.order_by]
    for i in reversed(range(len(rows.vals))):
        if i not in used:
            sort_cols.append(value_view(rows.vals[i]))
    for (c, desc), nl in zip(reversed(plan.order_by), reversed(nl_tup)):
        sort_cols.append(_ord_view(rows.vals[c], desc, nl))
    for k in reversed(rows.keys):
        sort_cols.append(value_view(k))
    sort_cols.append(rows.hashes)
    order = sort_perm(sort_cols)
    b = batch_permute(rows, order)
    d = (jnp.maximum(b.diffs, 0) * b.live).astype(DIFF_DTYPE)

    idx = jnp.arange(n)
    part_start = ~row_equal_prev((b.hashes, *b.keys))
    if plan.order_by:
        peer_start = part_start | ~row_equal_prev(
            tuple(b.vals[c] for c, _ in plan.order_by)
        )
    else:
        peer_start = part_start
    cum_incl = jnp.cumsum(d)
    total = cum_incl[-1]
    cum_before = cum_incl - d
    part_first = jax.lax.cummax(jnp.where(part_start, idx, -1))
    peer_first = jax.lax.cummax(jnp.where(peer_start, idx, -1))
    part_id = jnp.cumsum(part_start.astype(jnp.int32)) - 1
    peer_id = jnp.cumsum(peer_start.astype(jnp.int32)) - 1
    part_start_cnt = cum_before[part_first]
    peer_start_cnt = cum_before[peer_first]
    # instances through the end of the peer run / partition
    peer_end_cnt = jax.ops.segment_max(cum_incl, peer_id, num_segments=n)[peer_id]
    part_end_cnt = jax.ops.segment_max(cum_incl, part_id, num_segments=n)[part_id]
    peer_last_row = jax.ops.segment_max(idx, peer_id, num_segments=n)[peer_id]

    # -- expansion: one output instance per unit of multiplicity ------------
    j = jnp.arange(out_cap, dtype=cum_incl.dtype)
    src = jnp.clip(searchsorted(cum_incl, j, side="right"), 0, n - 1)
    valid = (j < total) & b.live[src]
    part_start_j = part_start_cnt[src]
    idx_in_part = j - part_start_j

    def frame_agg(spec: WindowFuncSpec):
        """Running aggregate over the default frame (through current peers)."""
        if spec.func == "count" and spec.arg is None:
            contrib = d
            nonnull = d
        else:
            col = b.vals[spec.arg]
            if col.dtype == jnp.bool_:
                col = col.astype(jnp.int8)
            null = _derived_null(col)
            nn = jnp.where(null, 0, 1).astype(DIFF_DTYPE) * d
            nonnull = nn
            if spec.func == "count":
                contrib = nn
            elif spec.func == "sum":
                if jnp.issubdtype(col.dtype, jnp.floating):
                    contrib = jnp.where(null, 0.0, col) * d.astype(col.dtype)
                else:
                    contrib = jnp.where(null, 0, col).astype(I64_DTYPE) * d
            else:  # min / max over the frame
                take_max = spec.func == "max"
                info_ext = (
                    jnp.asarray(-np.inf if take_max else np.inf, col.dtype)
                    if jnp.issubdtype(col.dtype, jnp.floating)
                    else jnp.asarray(
                        jnp.iinfo(col.dtype).min if take_max else jnp.iinfo(col.dtype).max,
                        col.dtype,
                    )
                )
                view = jnp.where(null | (d == 0), info_ext, col)
                run = _seg_scan_min(view, part_start, take_max)
                frame_val = run[peer_last_row]
                rc = jnp.cumsum(nn)
                frame_nn = rc[peer_last_row] - (rc[part_first] - nn[part_first])
                out_row = jnp.where(
                    frame_nn > 0, frame_val, _null_sentinel_arr(col.dtype)
                )
                return out_row[src]
        r = jnp.cumsum(contrib)
        frame_sum = r[peer_last_row] - (r[part_first] - contrib[part_first])
        if spec.func == "count":
            return frame_sum[src]
        rc = jnp.cumsum(nonnull)
        frame_nn = rc[peer_last_row] - (rc[part_first] - nonnull[part_first])
        out_row = jnp.where(
            frame_nn > 0,
            frame_sum,
            _null_sentinel_arr(frame_sum.dtype),
        )
        return out_row[src]

    func_cols = []
    for spec in plan.funcs:
        if spec.func == "row_number":
            out = idx_in_part + 1
        elif spec.func == "rank":
            out = peer_start_cnt[src] - part_start_j + 1
        elif spec.func == "dense_rank":
            out = (peer_id[src] - peer_id[part_first[src]] + 1).astype(I64_DTYPE)
        elif spec.func == "ntile":
            nt = jnp.asarray(spec.offset, I64_DTYPE)
            size = part_end_cnt[src] - part_start_j
            big = size - (size // nt) * nt  # parts with an extra row
            small_sz = size // nt
            cut = big * (small_sz + 1)
            out = jnp.where(
                idx_in_part < cut,
                idx_in_part // jnp.maximum(small_sz + 1, 1),
                big + (idx_in_part - cut) // jnp.maximum(small_sz, 1),
            ) + 1
        elif spec.func in ("lag", "lead"):
            col = b.vals[spec.arg]
            if col.dtype == jnp.bool_:
                col = col.astype(jnp.int8)
            off = jnp.asarray(spec.offset, j.dtype)
            t = j - off if spec.func == "lag" else j + off
            ok = (
                (t >= part_start_j)
                if spec.func == "lag"
                else (t < part_end_cnt[src])
            )
            src_t = src[jnp.clip(t, 0, out_cap - 1)]
            out = jnp.where(ok, col[src_t], _null_sentinel_arr(col.dtype))
        elif spec.func == "first_value":
            col = b.vals[spec.arg]
            if col.dtype == jnp.bool_:
                col = col.astype(jnp.int8)
            out = col[part_first[src]]
        elif spec.func == "last_value":
            col = b.vals[spec.arg]
            if col.dtype == jnp.bool_:
                col = col.astype(jnp.int8)
            out = col[peer_last_row[src]]
        elif spec.func in ("sum", "count", "min", "max"):
            out = frame_agg(spec)
        else:  # pragma: no cover
            raise NotImplementedError(spec.func)
        func_cols.append(out.astype(np.dtype(spec.out_dtype)))

    t_out = to_device_time(time)
    vals = tuple(jnp.where(valid, v[src], 0) for v in b.vals) + tuple(
        jnp.where(valid, c, jnp.zeros_like(c)) for c in func_cols
    )
    return UpdateBatch(
        hashes=jnp.where(valid, b.hashes[src], PAD_HASH),
        keys=(),
        vals=vals,
        times=jnp.where(valid, t_out, PAD_TIME),
        diffs=jnp.where(valid, 1, 0).astype(DIFF_DTYPE),
    )


@jax.jit
def _total_instances(rows: UpdateBatch) -> jnp.ndarray:
    return jnp.sum(jnp.maximum(rows.diffs, 0) * rows.live)


def window_step(arrangement, delta_keyed: UpdateBatch, plan: WindowPlan, time: int):
    """One tick: emits new_windows − old_windows for affected partitions.

    `arrangement` is keyed by plan.partition_cols; `delta_keyed` must be keyed
    the same way. This function inserts the delta.
    """
    from .consolidate import consolidate

    probes = distinct_keys(delta_keyed)
    vdt = tuple(v.dtype for v in delta_keyed.vals)
    old_rows = gather_groups(probes, arrangement.batches, time, vdt)
    arrangement.insert(delta_keyed, already_keyed=True)
    new_rows = gather_groups(probes, arrangement.batches, time, vdt)
    old_n = int(_total_instances(old_rows))
    new_n = int(_total_instances(new_rows))
    old_out = window_compute(old_rows, plan, time, bucket_cap(max(old_n, 1)))
    new_out = window_compute(new_rows, plan, time, bucket_cap(max(new_n, 1)))
    return consolidate(UpdateBatch.concat(new_out, negate(old_out)))
