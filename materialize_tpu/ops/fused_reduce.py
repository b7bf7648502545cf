"""Fused MFP→accumulable-reduce tick: ONE compiled program per update.

`SELECT keys…, sum/count(…) FROM src WHERE … GROUP BY keys` is the most
common materialized-view shape; the host-orchestrated path dispatches ~10
kernels per tick for it. This fuses filter/map evaluation, contribution
building, consolidation, state lookup, self-correcting emission and the
state merge into a single jitted function — the per-tick cost becomes one
dispatch plus one host count read (the design point of SURVEY.md §7: whole
steps under jit, host keeps only control).

The state merge is `reduce.merge_consolidate_accums`, the table update
`accumulable_step` makes too: the table leaves every step in packed-key
order and the delta is consolidated into it, so the delta is ranked into
the table (`search.merge_perm`) and the two merged in one pass over the
table, where sorting their concatenation again would sort the whole table
every step (2,097,152 rows a refresh for TPC-H Q18's per-order sum at SF1).
Its double-collision flag becomes a HASH_COLLISION_EXHAUSTED error row.

Capacity discipline: the caller keeps the state capacity STICKY (grow-only,
pow2), so the (state_cap, delta_cap) shape pairs recur and the jit cache
stays warm.
"""

from __future__ import annotations

from functools import partial

import jax

from ..expr.linear import MapFilterProject
from ..repr.batch import UpdateBatch
from .consolidate import consolidate
from .reduce import (
    AccumState,
    _contributions,
    _emit_output,
    accum_overflow_errs,
    collision_errs,
    consolidate_accums,
    lookup_accums,
    merge_consolidate_accums,
    step_counts,
)


def fused_mfp_reduce_step(
    state: AccumState,
    delta: UpdateBatch,
    time,
    mfp: MapFilterProject,
    key_cols: tuple[int, ...],
    aggs: tuple,
):
    """(state, Δin, t) → (state', Δout, Δerrs, counts) in one XLA program;
    `counts` is `reduce.step_counts` (live groups, groups whose output
    changed, Δerrs's live rows, the table merge's collision flag), so the
    caller's one host read needs no program of its own. Without key columns
    Δout is KEYLESS_OUT_CAP rows."""
    # forwards only: the harness wraps this un-jitted name and reads the
    # device program `jit__fused_mfp_reduce_step` (chipbench/metrics/)
    return _fused_mfp_reduce_step(state, delta, time, mfp, key_cols, aggs)


@partial(jax.jit, static_argnames=("mfp", "key_cols", "aggs"))
def _fused_mfp_reduce_step(
    state: AccumState,
    delta: UpdateBatch,
    time,
    mfp: MapFilterProject,
    key_cols: tuple[int, ...],
    aggs: tuple,
):
    if mfp.is_identity():
        oks, errs1 = delta, None
    else:
        oks, errs1 = mfp.apply(delta)
    raw, errs2 = _contributions(oks, key_cols, aggs)
    contrib = consolidate_accums(raw)
    _found, old_accums, old_nrows, missed = lookup_accums(state, contrib)
    new_state, dup = merge_consolidate_accums(state, contrib)
    errs2 = consolidate(
        UpdateBatch.concat(errs2, collision_errs(contrib, missed, time, dup))
    )
    ov = accum_overflow_errs(contrib, old_accums, aggs, time)
    if ov is not None:
        errs2 = consolidate(UpdateBatch.concat(errs2, ov))
    out = consolidate(
        _emit_output(contrib, old_accums, old_nrows, time, aggs, keyless=not key_cols)
    )
    errs = errs2 if errs1 is None else consolidate(UpdateBatch.concat(errs1, errs2))
    return new_state, out, errs, step_counts(new_state, contrib, old_nrows, errs, dup)
