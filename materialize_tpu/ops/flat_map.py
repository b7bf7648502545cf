"""FlatMap: per-row table functions (generate_series) as sized two-pass kernels.

The TPU analogue of the reference's FlatMap rendering
(src/compute/src/render/flat_map.rs): instead of a per-row emit loop, the
fan-out is the same two-pass shape as the sized join (ops/join.py) —

  pass 1 (count):       per-row series cardinality from the (lo, hi, step)
                        scalar expressions; prefix sum.
  pass 2 (materialize): output slot j maps back to (input row, offset) by
                        binary search over the prefix sums; the series value
                        is lo[row] + offset * step[row].

Rows with NULL arguments produce no series rows (pg semantics); step = 0 is
a per-row error routed to the errs stream (loud, not a trap). Static output
capacity on the fused path (overflow-flagged); the host path sizes by the
count pass.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..expr.scalar import EvalErr, eval_expr3
from ..repr.batch import I64_DTYPE, PAD_TIME, UpdateBatch
from ..repr.hashing import PAD_HASH
from .search import searchsorted


def _series_bounds(batch: UpdateBatch, exprs):
    """(lo, step, count[i64], err[i32]) per input row."""
    cols = list(batch.vals)
    n = batch.cap
    lo, lnull, lerr = eval_expr3(exprs[0], cols, n)
    hi, hnull, herr = eval_expr3(exprs[1], cols, n)
    st, snull, serr = eval_expr3(exprs[2], cols, n)
    lo = lo.astype(I64_DTYPE)
    hi = hi.astype(I64_DTYPE)
    st = st.astype(I64_DTYPE)
    null = lnull | hnull | snull
    err = jnp.maximum(jnp.maximum(lerr, herr), serr)
    err = jnp.where(null, 0, err)
    step_zero = (st == 0) & ~null
    err = jnp.where(step_zero, jnp.int32(EvalErr.STEP_ZERO), err)
    safe = jnp.where(st == 0, jnp.ones_like(st), st)
    span_ok = ((st > 0) & (hi >= lo)) | ((st < 0) & (hi <= lo))
    count = jnp.where(span_ok, (hi - lo) // safe + 1, 0)
    ok = batch.live & ~null & (err == 0)
    count = jnp.where(ok, count, 0)
    err = jnp.where(batch.live, err, 0)
    return lo, st, count, err


@partial(jax.jit, static_argnames=("exprs",))
def flat_map_total(batch: UpdateBatch, exprs) -> jnp.ndarray:
    _lo, _st, count, _err = _series_bounds(batch, exprs)
    return jnp.sum(count)


def flat_map_materialize(batch: UpdateBatch, exprs, out_cap: int):
    """Returns (out, errs, overflow): out rows = input vals ++ series value."""
    return _flat_map_materialize(batch, exprs, out_cap)


@partial(jax.jit, static_argnames=("exprs", "out_cap"))
def _flat_map_materialize(batch: UpdateBatch, exprs, out_cap: int):
    lo, st, count, err = _series_bounds(batch, exprs)
    cum = jnp.cumsum(count)
    total = cum[-1] if count.shape[0] > 0 else jnp.zeros((), dtype=cum.dtype)
    over = total > out_cap

    j = jnp.arange(out_cap, dtype=cum.dtype)
    pi = searchsorted(cum, j, side="right")
    pi = jnp.minimum(pi, batch.cap - 1)
    prev = jnp.where(pi > 0, cum[pi - 1], 0)
    off = j - prev
    value = lo[pi] + off * st[pi]
    valid = j < total

    diffs = jnp.where(valid, batch.diffs[pi], 0)
    out = UpdateBatch(
        hashes=jnp.where(valid, jnp.zeros_like(batch.hashes[pi]), PAD_HASH),
        keys=(),
        vals=tuple(v[pi] for v in batch.vals) + (value,),
        times=jnp.where(valid, batch.times[pi], PAD_TIME),
        diffs=diffs,
    )
    err_mask = err != 0
    errs = UpdateBatch(
        hashes=jnp.where(err_mask, jnp.zeros_like(batch.hashes), PAD_HASH),
        keys=(),
        vals=(err.astype(I64_DTYPE),),
        times=jnp.where(err_mask, batch.times, PAD_TIME),
        diffs=jnp.where(err_mask, batch.diffs, 0),
    )
    return out, errs, over
