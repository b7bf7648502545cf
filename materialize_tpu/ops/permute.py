"""Fused multi-column permute-gather: one index vector over a whole payload.

The r2 TPU trace charged ~0.65 s of a 2.05 s Q3 tick to consolidate gathers:
every `UpdateBatch.permute` / probe-index materialization issued ~10 separate
XLA gathers, one per payload column. `multi_take` applies ONE index vector to
the whole column set grouped by dtype: each same-dtype group is stacked into
a (k, n) matrix and gathered once (`mat[:, idx]`) — one gather per dtype
instead of one per column. Stack→gather→unstack moves bits, never transforms
them, so outputs are byte-identical to per-column `col[idx]`.

Out-of-range indices clamp (`mode="clip"`), matching jnp's advanced-indexing
behavior at the existing call sites (which pre-clip anyway).
"""

from __future__ import annotations

import jax.numpy as jnp

from ..repr.batch import UpdateBatch


def _group_by_dtype(cols: tuple) -> list[tuple]:
    groups: dict = {}
    for i, c in enumerate(cols):
        groups.setdefault(jnp.dtype(c.dtype), []).append(i)
    return list(groups.items())


def multi_take(cols: tuple, idx: jnp.ndarray) -> tuple:
    """Gather every column at `idx`, dtype-grouped."""
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


def batch_permute(batch: UpdateBatch, perm: jnp.ndarray) -> UpdateBatch:
    """`UpdateBatch.permute` through the fused multi-column gather."""
    nk, nv = len(batch.keys), len(batch.vals)
    cols = (batch.hashes, *batch.keys, *batch.vals, batch.times, batch.diffs)
    g = multi_take(cols, perm)
    return UpdateBatch(
        g[0], tuple(g[1 : 1 + nk]), tuple(g[1 + nk : 1 + nk + nv]), g[-2], g[-1]
    )
