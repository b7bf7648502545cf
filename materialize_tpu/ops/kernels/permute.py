"""fused multi-column permute-gather: one index vector over a whole payload.

The r2 TPU trace charged ~0.65 s of a 2.05 s Q3 tick to consolidate gathers:
every `UpdateBatch.permute` / probe-index materialization issued ~10 separate
XLA gathers, one per payload column. Both backends here apply ONE index
vector to the whole column set grouped by dtype:

- **XLA**: stack each same-dtype column group into a (k, n) matrix and gather
  once per group (`mat[:, idx]`) — one gather per dtype instead of one per
  column, even where Pallas is off. Stack→gather→unstack moves bits, never
  transforms them, so outputs are byte-identical to per-column `col[idx]`.
- **Pallas**: the same dtype-grouped (k, n) matrix and the index vector land
  in VMEM once and the kernel emits the gathered (k, m) tile in a single
  pass, instead of re-streaming the index per column.

Out-of-range indices clamp (`mode="clip"`), matching jnp's advanced-indexing
behavior at the existing call sites (which pre-clip anyway).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import registry


def _group_by_dtype(cols: tuple) -> list[tuple]:
    groups: dict = {}
    for i, c in enumerate(cols):
        groups.setdefault(jnp.dtype(c.dtype), []).append(i)
    return list(groups.items())


def _xla_multi_take(cols: tuple, idx: jnp.ndarray) -> tuple:
    cols = tuple(cols)
    if not cols:
        return ()
    out: list = [None] * len(cols)
    for _dt, pos in _group_by_dtype(cols):
        if len(pos) == 1:
            out[pos[0]] = cols[pos[0]][idx]
            continue
        mat = jnp.stack([cols[i] for i in pos])
        g = jnp.take(mat, idx, axis=1, mode="clip")
        for j, i in enumerate(pos):
            out[i] = g[j]
    return tuple(out)


def _take_group_kernel(mat_ref, idx_ref, out_ref):
    idx = idx_ref[...][0]
    out_ref[...] = jnp.take(mat_ref[...], idx, axis=1, mode="clip")


def _pallas_multi_take(cols: tuple, idx: jnp.ndarray) -> tuple:
    cols = tuple(cols)
    if not cols:
        return ()
    m = int(idx.shape[0])
    n = int(cols[0].shape[0])
    if m == 0 or n == 0:
        return _xla_multi_take(cols, idx)
    idx2 = idx.astype(jnp.int32).reshape(1, m)
    out: list = [None] * len(cols)
    for dt, pos in _group_by_dtype(cols):
        k = len(pos)
        work = jnp.stack([cols[i] for i in pos])
        if dt == jnp.bool_:
            # bool tiles gather as int8 and cast back (bitwise no-op)
            work = work.astype(jnp.int8)
        g = pl.pallas_call(
            _take_group_kernel,
            out_shape=jax.ShapeDtypeStruct((k, m), work.dtype),
            interpret=registry.pallas_interpret(),
        )(work, idx2)
        if dt == jnp.bool_:
            g = g.astype(jnp.bool_)
        for j, i in enumerate(pos):
            out[i] = g[j]
    return tuple(out)


registry.register_kernel(
    "multi_take", xla=_xla_multi_take, pallas=_pallas_multi_take
)


def multi_take(cols: tuple, idx: jnp.ndarray) -> tuple:
    """Gather every column at `idx` via the active backend, dtype-grouped."""
    return registry.dispatch("multi_take", cols, idx)


def batch_permute(batch, perm: jnp.ndarray):
    """`UpdateBatch.permute` through the fused multi-column gather."""
    from ...repr.batch import UpdateBatch

    nk, nv = len(batch.keys), len(batch.vals)
    cols = (batch.hashes, *batch.keys, *batch.vals, batch.times, batch.diffs)
    g = multi_take(cols, perm)
    return UpdateBatch(
        g[0], tuple(g[1 : 1 + nk]), tuple(g[1 + nk : 1 + nk + nv]), g[-2], g[-1]
    )
