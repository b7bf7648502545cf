"""Consolidation: sort updates and sum diffs of identical (key, val, time) rows.

The TPU analogue of differential's `consolidate_updates` and of spine batch
merging (reference hot loop list: SURVEY.md §3.2) — ONE fused XLA program:
order by a packed u64 (key_hash<<32 | row_hash) with time as tiebreak,
segmented prefix-sum of diffs over equal-row runs, annihilated (diff==0) rows
masked to padding and compacted to the front. O(n log n) once per batch —
and, critically, NOT per merge: two batches that are already in canonical
order merge in O(n) via `merge_consolidate` (the shorter side ranked into
the longer, no sort), and live rows compact in O(n) via a cumsum stable
partition instead of an argsort. The r4 profile showed the per-tick
consolidation sorts were ~70% of tick time; the merge/compact paths remove
the sorts whose inputs are already ordered.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..repr.batch import PAD_TIME, UpdateBatch, bucket_cap
from ..repr.hashing import PAD_HASH
from .permute import batch_permute
from .search import merge_perm, sort_perm


def row_equal_prev(cols) -> jnp.ndarray:
    """eq[i] = all columns equal between row i and i-1 (eq[0] = False).

    Shared run-detection primitive for every sorted-run kernel (consolidate,
    accumulator merge, distinct-keys). Columns are canonicalized via _cmp_view.
    """
    eq = None
    for raw in cols:
        c = _cmp_view(raw)
        e = c[1:] == c[:-1]
        eq = e if eq is None else (eq & e)
    return jnp.concatenate([jnp.zeros((1,), dtype=jnp.bool_), eq])


def pack_sort_key(batch: UpdateBatch) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The canonical ordering key as a (key_hash, row_hash) u32 pair.

    row_hash is a u32 content hash of the val columns, so duplicate rows
    inside one key group land adjacent and annihilate. The pair orders
    exactly like the former packed u64 `(key_hash << 32) | row_hash` — two
    native u32 sort operands instead of one split u64 (the TPU VPU is a
    32-bit machine; u64 sort operands cost 2× in X64SplitLow pairs). PAD_HASH
    rows carry the maximal hi key (hash_columns clamps live hashes below
    PAD_HASH), so padding sorts last. A batch sorted by this pair is sorted
    by key hash — exactly what binary-search probes need.
    """
    from ..repr.hashing import hash_columns

    if batch.vals:
        row_hash = hash_columns(batch.vals)
    else:
        row_hash = jnp.zeros_like(batch.hashes)
    return batch.hashes, row_hash


def _stable_partition_perm(live: jnp.ndarray) -> jnp.ndarray:
    """Permutation moving live rows to the front, stably, in O(n).

    Equivalent to argsort(~live, stable=True) without the sort: target slots
    come from two cumsums, and the gather permutation is their scatter
    inverse. (Init arrays derive from the data so varying manual axes match
    under shard_map.)
    """
    li = live.astype(jnp.int32)
    front = jnp.cumsum(li) - 1
    total = front[-1] + 1
    back = total + jnp.cumsum(1 - li) - 1
    pos = jnp.where(live, front, back)
    iota = jnp.arange(pos.shape[0], dtype=pos.dtype)
    return (pos * 0).at[pos].set(iota)


def _filled_like(col: jnp.ndarray, cap: int, fill) -> jnp.ndarray:
    """A (cap,)-shaped fill array whose varying axes derive from `col`."""
    seed = jnp.where(jnp.zeros((1,), jnp.bool_), col[:1], jnp.asarray(fill, col.dtype))
    return jnp.broadcast_to(seed, (cap,))


@partial(jax.jit, static_argnames=("cap",))
def compact_to(batch: UpdateBatch, cap: int):
    """O(n) compaction of live rows into a fresh batch of capacity `cap`.

    Returns (batch', overflow). Order among live rows is preserved (a sorted
    input stays sorted); rows beyond `cap` are dropped with the overflow flag
    raised — callers must treat an overflowing compaction as a failed tick,
    exactly like an arrangement-capacity overflow. This is what lets fused
    ticks concatenate K wide operator outputs and then sort only the small
    live prefix instead of the full static capacity.
    """
    live = batch.live
    pos = jnp.cumsum(live.astype(jnp.int32)) - 1
    total = pos[-1] + 1
    over = total > cap
    idx = jnp.where(live, pos, cap)  # dead (and overflowing) rows drop

    def scat(col, fill):
        return _filled_like(col, cap, fill).at[idx].set(col, mode="drop")

    out = UpdateBatch(
        scat(batch.hashes, PAD_HASH),
        tuple(scat(k, 0) for k in batch.keys),
        tuple(scat(v, 0) for v in batch.vals),
        scat(batch.times, PAD_TIME),
        scat(batch.diffs, 0),
    )
    return out, over


def gather_parts(fetch, counts: list[int], floor: int = 0) -> list[UpdateBatch]:
    """The parts `fetch(i, cap)` brings back, at capacity `cap`, of sources
    whose matching rows the host counted as `counts`. Where those rows fit
    `floor` in all (none at all too, where there is a source), every
    source's part comes back at that capacity, one that counted nothing as
    well, and they are compacted into ONE batch of it: its shape then
    follows neither how many rows there are nor which sources hold them, so
    a refresh whose selective stage first lets a row through meets the
    programs every refresh before it ran. Else each source that counted
    rows comes back at its own bucket. `floor` 0 asks for the latter."""
    if floor and sum(counts) <= floor and counts:
        acc = None
        for i in range(len(counts)):  # also one that matched nothing: its turn comes
            part = fetch(i, floor)
            acc = part if acc is None else UpdateBatch.concat(acc, part)
        return [acc if acc.cap == floor else compact_to(acc, floor)[0]]
    return [fetch(i, bucket_cap(n)) for i, n in enumerate(counts) if n]


def run_sum(run_start: jnp.ndarray, cols: tuple) -> tuple:
    """Segmented sum by run over a canonically ordered batch: per column,
    ``out[i] = run_total if run_start[i] else 0`` (a cumsum, a scatter-add
    and a gather). Also sums `consolidate_accums`' tables (ops/reduce.py)."""
    n = int(run_start.shape[0])
    seg = jnp.cumsum(run_start.astype(jnp.int32)) - 1
    return tuple(
        jnp.where(run_start, jax.ops.segment_sum(c, seg, num_segments=n)[seg], 0)
        for c in cols
    )


def _consolidate_sorted(b: UpdateBatch, compact: bool) -> UpdateBatch:
    """Run-merge + mask tail shared by `consolidate` and `merge_consolidate`.

    Requires `b` ordered so equal (key, row, time) rows are adjacent."""
    cmp_cols = [b.hashes, *b.keys, *b.vals, b.times]
    same = row_equal_prev(cmp_cols)
    run_start = ~same
    # run totals at run starts, 0 elsewhere
    (diff_out,) = run_sum(run_start, (b.diffs,))

    live = run_start & (diff_out != 0) & (b.hashes != PAD_HASH)
    diffs = jnp.where(live, diff_out, 0)
    if not compact:
        return UpdateBatch(b.hashes, b.keys, b.vals, b.times, diffs)

    hashes = jnp.where(live, b.hashes, PAD_HASH)
    keys = tuple(jnp.where(live, k, jnp.zeros_like(k)) for k in b.keys)
    vals = tuple(jnp.where(live, v, jnp.zeros_like(v)) for v in b.vals)
    times = jnp.where(live, b.times, PAD_TIME)

    perm = _stable_partition_perm(live)
    return batch_permute(UpdateBatch(hashes, keys, vals, times, diffs), perm)


@partial(jax.jit, static_argnames=("compact",))
def _consolidate(batch: UpdateBatch, compact: bool) -> UpdateBatch:
    k_hi, k_lo = pack_sort_key(batch)
    order = sort_perm((batch.times, k_lo, k_hi))
    return _consolidate_sorted(batch_permute(batch, order), compact)


def consolidate(batch: UpdateBatch, compact: bool = True) -> UpdateBatch:
    """Canonicalize a batch: hash-sorted, equal rows merged, no zero diffs.

    The sort key is (packed u64 key, time-view) — 2 fixed operands instead of
    the full row (TPU sorts cost per 32-bit operand in both runtime and
    compile time; this is the single hottest kernel). See `pack_sort_key`:
    duplicate rows inside one key group land adjacent and annihilate;
    equal-row runs are then confirmed by full-row adjacent comparison, which
    keeps correctness under hash collisions — colliding distinct rows merely
    stay split across entries, and every consumer treats a batch as a
    multiset of (row, time, diff) updates (operators are linear in diff), so
    only perfect annihilation (a capacity concern, not correctness) needs
    adjacency. The time operand is the u32 device time view directly — three
    native u32 sort operands total, no 64-bit operand anywhere in the sort.

    Padding rows sort last (PAD_HASH) and keep diff 0, so they fold into one
    run that is masked back out. Output has the same capacity.

    With ``compact=False`` the compaction pass is skipped: annihilated rows
    keep their hash/time in place with diff forced to 0, so the output is
    STILL hash-sorted and probe-able but dead rows occupy interior slots. Use
    for probe streams and operator outputs — anything not about to be
    capacity-shrunk (`with_capacity` truncation needs live rows in front, so
    arrangement level contents keep compact=True). Dead rows are inert
    everywhere (consumers test diff != 0) but DO widen join candidate ranges,
    so arrangements should stay compacted.
    """
    # forwards only: the harness wraps this un-jitted name and reads the
    # device program `jit__consolidate` (chipbench/metrics/kernels_roofline.json)
    return _consolidate(batch, compact)


@partial(jax.jit, static_argnames=("out_cap",))
def _merge_consolidate(
    a: UpdateBatch, b: UpdateBatch, since, out_cap: int | None = None
) -> UpdateBatch:
    ka_hi, ka_lo = pack_sort_key(a)
    kb_hi, kb_lo = pack_sort_key(b)
    perm = merge_perm(ka_hi, ka_lo, kb_hi, kb_lo)
    cat = batch_permute(UpdateBatch.concat(a, b), perm)
    if since is not None:
        cat = advance_times(cat, since)
    out = _consolidate_sorted(cat, compact=True)
    # pad or truncate inside the program: no eager per-column programs
    # after the merge, and the output capacity is part of the one key
    return out if out_cap is None else out.with_capacity(out_cap)


def merge_consolidate(
    a: UpdateBatch,
    b: UpdateBatch,
    since: jnp.ndarray | None = None,
    out_cap: int | None = None,
) -> UpdateBatch:
    """Merge two batches that are ALREADY in canonical order, in O(n).

    The LSM merge fast path: both inputs are `consolidate` outputs (every
    spine level and every arranged delta is), so instead of re-sorting the
    concatenation the merged order comes from `merge_perm` over the packed
    keys: one searchsorted pass of the SHORTER side into the longer, a mark
    of the slots it takes and a prefix sum — the differential spine's cursor
    merge (src/compute/src/render/join/mz_join_core.rs-adjacent batch
    merger), vectorized. A head merge (T, T/16) searches its delta's rows
    only. Output capacity = a.cap + b.cap, live rows compacted to the
    front, or `out_cap` when given: padded, or truncated, which is sound only
    if the caller knows the live rows fit (rows beyond `out_cap` are dropped
    unseen — the spine's head keeps a host-side bound, arrangement/spine.py).

    With `since`, times first advance to the compaction frontier so +/- pairs
    at bygone times cancel. Annihilation nuance: within one packed-key
    cluster the merged order is a's rows then b's; when a and b hold equal
    rows at *different interleaved* times the pairs may not touch — they
    still cancel once `since` passes both (times then collapse equal), so
    this costs capacity transiently, never correctness (multiset semantics).
    """
    # forwards only, for the same reason as `consolidate`
    return _merge_consolidate(a, b, since, out_cap)


def _cmp_view(c: jnp.ndarray) -> jnp.ndarray:
    from ..repr.hashing import value_view

    return value_view(c)


@jax.jit
def advance_times(batch: UpdateBatch, since: jnp.ndarray):
    """Logical compaction: forward every live time to at least `since`.

    Mirrors differential trace compaction under an advanced `since` frontier
    (reference: allow_compaction, src/compute/src/compute_state.rs:732). After
    advancing, `consolidate` can cancel updates that now share a timestamp.
    """
    from ..repr.batch import to_device_time

    since = to_device_time(since)
    is_pad = batch.times == PAD_TIME
    new_times = jnp.where(is_pad, batch.times, jnp.maximum(batch.times, since))
    return UpdateBatch(batch.hashes, batch.keys, batch.vals, new_times, batch.diffs)
