"""Accumulable reductions (SUM / COUNT family) as segmented device kernels.

The TPU analogue of the reference's Accumulable reduce plan
(src/compute/src/render/reduce.rs:2067-2268 `Accum` semigroup): per-key state
is a sorted singleton table of accumulator vectors; a tick's delta batch is
segment-summed into per-key contributions, merged into the table, and the
output delta is emitted self-correctingly as (-old_aggregate, +new_aggregate)
per affected key — pairs that didn't change cancel in consolidation.

MIN/MAX (hierarchical) and general "basic" reductions live in topk.py /
hierarchical kernels; AVG etc. are planned as SUM+COUNT plus a post-MFP,
exactly as the reference plans them (src/compute-types/src/plan/reduce.rs:130).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..expr.scalar import ScalarExpr, eval_expr
from ..repr.batch import (
    DIFF_DTYPE,
    I64_DTYPE,
    PAD_TIME,
    UpdateBatch,
    bucket_cap,
    to_device_time,
)
from ..repr.hashing import PAD_HASH, hash_columns
from .consolidate import _stable_partition_perm, row_equal_prev, run_sum
from .permute import multi_take
from .search import merge_perm, merge_rank, searchsorted, sort_perm

# Fast-path scan width for hash-bucket lookups. u32 row hashes make small
# buckets routine at scale (birthday collisions from ~2^16 keys), so lookups
# scan 4 slots unconditionally and — only when some probe's bucket is larger
# — re-scan at _WIDE_HASH_COLLISIONS under lax.cond (probe widening: the
# wide path costs nothing unless triggered). A >64-deep bucket needs a
# ~5-way u32 collision (P < 1e-11 at 60M uniform keys) and still errors
# loudly rather than mis-aggregating.
_MAX_HASH_COLLISIONS = 4
_WIDE_HASH_COLLISIONS = 64


@jax.tree_util.register_pytree_node_class
@dataclass
class AccumState:
    """Per-key accumulators: one row per live key, sorted by (hash, keys)."""

    hashes: jnp.ndarray  # u32 [cap], PAD_HASH = padding
    keys: tuple  # key columns [cap]
    accums: tuple  # one accumulator column per aggregate [cap]
    nrows: jnp.ndarray  # i64 (DIFF_DTYPE) [cap] — group size (sum of diffs)

    def tree_flatten(self):
        return (self.hashes, self.keys, self.accums, self.nrows), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def cap(self) -> int:
        return int(self.hashes.shape[0])

    @property
    def live(self) -> jnp.ndarray:
        return self.hashes != PAD_HASH

    def count(self) -> jnp.ndarray:
        return jnp.sum(self.live.astype(jnp.int32))

    @staticmethod
    def empty(cap: int, key_dtypes, accum_dtypes) -> "AccumState":
        return AccumState(
            hashes=jnp.full((cap,), PAD_HASH, dtype=jnp.uint32),
            keys=tuple(jnp.zeros((cap,), dtype=dt) for dt in key_dtypes),
            accums=tuple(jnp.zeros((cap,), dtype=dt) for dt in accum_dtypes),
            nrows=jnp.zeros((cap,), dtype=DIFF_DTYPE),
        )

    @staticmethod
    def concat(a: "AccumState", b: "AccumState") -> "AccumState":
        return AccumState(
            jnp.concatenate([a.hashes, b.hashes]),
            tuple(jnp.concatenate([x, y]) for x, y in zip(a.keys, b.keys)),
            tuple(jnp.concatenate([x, y]) for x, y in zip(a.accums, b.accums)),
            jnp.concatenate([a.nrows, b.nrows]),
        )

    def with_capacity(self, cap: int) -> "AccumState":
        cur = self.cap
        if cap == cur:
            return self
        if cap < cur:
            return AccumState(
                self.hashes[:cap],
                tuple(k[:cap] for k in self.keys),
                tuple(a[:cap] for a in self.accums),
                self.nrows[:cap],
            )
        pad = cap - cur

        def ext(a, fill):
            return jnp.concatenate([a, jnp.full((pad,), fill, dtype=a.dtype)])

        return AccumState(
            ext(self.hashes, PAD_HASH),
            tuple(ext(k, 0) for k in self.keys),
            tuple(ext(a, 0) for a in self.accums),
            ext(self.nrows, 0),
        )

    def rebucketed(self) -> "AccumState":
        """A consolidated table at the pow2 bucket of its live rows.

        A step leaves the table at cap(state) + cap(delta); held there, the
        capacity (a shape, so a new program for every kernel that sees it)
        follows the number of ticks instead of the number of groups."""
        return self.with_capacity(bucket_cap(int(self.count())))


@dataclass(frozen=True)
class AggregateExpr:
    """One aggregate: func in {sum, count}; expr evaluated over the input row.

    Mirrors the accumulable subset of the reference's `AggregateFunc`
    (src/expr/src/relation/func.rs:1878).

    `fixed_scale` > 0 marks a FLOAT sum accumulated in fixed point: each
    input is scaled by 2**fixed_scale, rounded to the i64 accumulator, and
    the emitted output column descales back to float32. Insert and retract
    of the same value quantize identically, so retractions cancel EXACTLY —
    an f32/f64 running sum would drift under churn. This is the reference's
    float accumulation strategy (src/compute/src/render/reduce.rs:2067-2268
    `Accum::Float` scales by 2^24 into a wide integer) rebuilt for the TPU's
    integer units. Magnitude bound: |sum * 2^24| must fit i64, i.e. total
    |sum| < ~5.5e11; overflow wraps (documented engine limit, vs the
    reference's i128 headroom).
    """

    func: str
    expr: ScalarExpr
    accum_dtype: str = "int64"
    fixed_scale: int = 0


FLOAT_FIXED_SCALE = 24  # same quantum as the reference's float_scale


def agg_out_dtype(a: AggregateExpr) -> np.dtype:
    """Output column dtype of one aggregate (accumulator dtype, except
    fixed-point float sums which descale to f32 on emission)."""
    return np.dtype(np.float32) if a.fixed_scale else np.dtype(a.accum_dtype)


def _accum_pack(s: AccumState) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Canonical ordering key of an accum table as a (key_hash, mix) u32 pair.

    Orders exactly like the former packed u64 `(key_hash << 32) | mix`, as
    two native u32 operands. Sorting by this (with the raw keys as tiebreak
    in the sort path) makes two independently consolidated tables mergeable
    by a single two-key searchsorted pass: rows from different tables that
    agree on the full pair but hold different keys need a 2^-64
    double-collision, which merge_consolidate_accums detects and flags
    rather than mis-merging. PAD rows carry the maximal hi key
    (hash_columns clamps below PAD_HASH).
    """
    from ..repr.hashing import mix_columns

    if s.keys:
        mix = mix_columns(s.keys)
    else:
        mix = jnp.zeros_like(s.hashes)
    return s.hashes, mix


def _accum_take(s: AccumState, idx: jnp.ndarray) -> AccumState:
    """Gather every AccumState column at `idx` via the fused multi-column
    gather — one dtype-grouped pass instead of one XLA gather per column."""
    nk = len(s.keys)
    g = multi_take((s.hashes, *s.keys, *s.accums, s.nrows), idx)
    return AccumState(g[0], tuple(g[1 : 1 + nk]), tuple(g[1 + nk : -1]), g[-1])


def _consolidate_accums_sorted(s: AccumState):
    """Run-merge + compaction tail over a packed-key-ordered table.

    Run boundaries come from full (hash, keys) row comparison, so equal keys
    must land adjacent (the sort path's keys tiebreak). Returns (state',
    dup), `dup` as `_unmerged_keys` reads it."""
    run_start = ~row_equal_prev((s.hashes, *s.keys))
    # segmented sum by run over every accumulator plus nrows at once
    summed = run_sum(run_start, (*s.accums, s.nrows))
    accums, nrows = summed[:-1], summed[-1]
    live = run_start & _nonzero(accums, nrows) & (s.hashes != PAD_HASH)
    hashes = jnp.where(live, s.hashes, PAD_HASH)
    keys = tuple(jnp.where(live, k, jnp.zeros_like(k)) for k in s.keys)
    accums = tuple(jnp.where(live, a, jnp.zeros_like(a)) for a in accums)
    nrows = jnp.where(live, nrows, 0)
    perm = _stable_partition_perm(live)
    out = _accum_take(AccumState(hashes, keys, accums, nrows), perm)
    return out, _unmerged_keys(out)


def _nonzero(accums, nrows) -> jnp.ndarray:
    """A group is held while its row count or any accumulator is nonzero."""
    nonzero = nrows != 0
    for a in accums:
        nonzero = nonzero | (a != 0)
    return nonzero


def _unmerged_keys(out: AccumState) -> jnp.ndarray:
    """Whether a compacted table holds a live key twice: possible only via a
    packed-key double collision between the sides of a merge, whose
    unmerged rows sit within a few slots of each other (a double-collision
    cluster holds 2 distinct keys from each side). Callers surface it as a
    failed step."""
    from ..repr.hashing import value_view

    dup = out.count() < 0  # varying-typed False
    for d in (1, 2, 3):
        eq = (out.hashes[d:] == out.hashes[:-d]) & (out.hashes[d:] != PAD_HASH)
        for k in out.keys:
            kv = value_view(k)
            eq = eq & (kv[d:] == kv[:-d])
        dup = dup | jnp.any(eq)
    return dup


@jax.jit
def _consolidate_accums(s: AccumState) -> AccumState:
    p_hi, p_lo = _accum_pack(s)
    order = sort_perm((*(k for k in reversed(s.keys)), p_lo, p_hi))
    out, _dup = _consolidate_accums_sorted(_accum_take(s, order))
    return out


def consolidate_accums(s: AccumState) -> AccumState:
    """Order by (packed key, keys), sum accumulators of equal keys, drop
    empty groups. Keys tiebreak the sort, so equal keys are always adjacent
    here (no collision exposure on this path)."""
    return _consolidate_accums(s)


@jax.jit
def _merge_consolidate_accums(a: AccumState, b: AccumState):
    ka_hi, ka_lo = _accum_pack(a)
    kb_hi, kb_lo = _accum_pack(b)
    rank = merge_rank(ka_hi, ka_lo, kb_hi, kb_lo)
    perm = merge_perm(ka_hi, ka_lo, kb_hi, kb_lo, rank)
    # Each side holds a key once, so a key's run in the merged order is at
    # most its `a` row and then its `b` row, and the one row of the longer
    # side a row of the shorter can meet is its merged neighbour, which
    # `rank` names: the pairs are found from the shorter side alone. A pair's
    # total goes to its `a` row (the run's start, whose key bits the sort
    # path keeps) and its `b` row dies, so the payload is gathered ONCE,
    # straight into its compacted slot.
    na, nb = a.hashes.shape[0], b.hashes.shape[0]
    if nb <= na:
        ia, ib = jnp.clip(rank - 1, 0, na - 1), jax.lax.iota(jnp.int32, nb)
    else:
        ia, ib = jax.lax.iota(jnp.int32, na), jnp.clip(rank, 0, nb - 1)
    from ..repr.hashing import value_view

    pair = (a.hashes[ia] == b.hashes[ib]) & (b.hashes[ib] != PAD_HASH)
    for ka, kb in zip(a.keys, b.keys):
        pair = pair & (value_view(ka[ia]) == value_view(kb[ib]))
    a_vals, b_vals = (*a.accums, a.nrows), (*b.accums, b.nrows)
    if nb <= na:
        added = tuple(
            x.at[ia].add(jnp.where(pair, y, 0), mode="promise_in_bounds")
            for x, y in zip(a_vals, b_vals)
        )
        b_dead = pair
    else:
        added = tuple(
            x + jnp.where(pair, y, 0) for x, y in zip(a_vals, multi_take(b_vals, ib))
        )
        b_dead = (b.hashes != b.hashes).at[jnp.where(pair, ib, nb)].set(True, mode="drop")
    a_live = (a.hashes != PAD_HASH) & _nonzero(added[:-1], added[-1])
    b_live = (b.hashes != PAD_HASH) & ~b_dead & _nonzero(b_vals[:-1], b_vals[-1])
    live = jnp.concatenate([a_live, b_live])[perm]  # in merged order
    src = perm[_stable_partition_perm(live)]
    both = AccumState.concat(AccumState(a.hashes, a.keys, added[:-1], added[-1]), b)
    g = _accum_take(both, src)
    held = jax.lax.iota(jnp.int32, na + nb) < jnp.sum(live.astype(jnp.int32))
    out = AccumState(
        jnp.where(held, g.hashes, PAD_HASH),
        tuple(jnp.where(held, k, jnp.zeros_like(k)) for k in g.keys),
        tuple(jnp.where(held, x, jnp.zeros_like(x)) for x in g.accums),
        jnp.where(held, g.nrows, 0),
    )
    return out, _unmerged_keys(out)


def merge_consolidate_accums(a: AccumState, b: AccumState):
    """O(n) merge of two consolidated accum tables by packed key.

    Returns (state', dup), state' at cap(a) + cap(b) with its live rows
    first. Both inputs must be consolidate_accums / merge_consolidate_accums
    outputs (packed-key order, unique live keys). A reduce step merges its
    consolidated delta into its table here (`accumulable_step`,
    `fused_reduce._fused_mfp_reduce_step`): only the shorter side is ranked
    into the other (`merge_perm`), where sorting the concatenation again
    would sort both. Without a double collision state' is
    `consolidate_accums(AccumState.concat(a, b))` bit for bit. `dup` is the
    loud-failure flag for the 2^-64 packed-key double collision (see
    _accum_pack): a reduce step turns it into a HASH_COLLISION_EXHAUSTED
    error row (`collision_errs`), the LSM treats it like a capacity
    overflow; never a silent mis-aggregation."""
    return _merge_consolidate_accums(a, b)


@partial(jax.jit, static_argnames=("key_cols", "aggs"))
def _contributions(delta: UpdateBatch, key_cols: tuple[int, ...], aggs):
    """Per-row aggregate contributions of a raw delta batch (unconsolidated).

    Returns (AccumState, err_batch): rows whose aggregate input expression
    errors (e.g. division by zero) contribute nothing and are routed to the
    error batch, per the oks/errs twin-stream design.
    """
    cols = list(delta.vals)
    n = delta.cap
    keys = tuple(delta.vals[i] for i in key_cols)
    if keys:
        hashes = jnp.where(delta.live, hash_columns(keys), PAD_HASH)
    else:
        hashes = jnp.where(delta.live, jnp.zeros_like(delta.hashes), PAD_HASH)
    from ..expr.scalar import Literal, eval_expr3

    err = jnp.zeros((n,), dtype=jnp.int32)
    accums = []
    for agg in aggs:
        if agg.func == "count":
            dt = np.dtype(agg.accum_dtype)
            if isinstance(agg.expr, Literal) and agg.expr.value is not None:
                # count(*): every row counts
                accums.append(delta.diffs.astype(dt))
            else:
                # count(x): NULL inputs don't count (SQL aggregate rule)
                v, nv, ev = eval_expr3(agg.expr, cols, n)
                err = jnp.maximum(err, ev)
                accums.append(jnp.where(nv, 0, delta.diffs).astype(dt))
        elif agg.func == "sum":
            v, nv, ev = eval_expr3(agg.expr, cols, n)
            err = jnp.maximum(err, ev)
            dt = np.dtype(agg.accum_dtype)
            if agg.fixed_scale:
                # float sum: quantize once per value; exact under retraction
                q = jnp.round(
                    v.astype(jnp.float32) * np.float32(1 << agg.fixed_scale)
                ).astype(dt)
                contrib = q * delta.diffs.astype(dt)
            else:
                contrib = v.astype(dt) * delta.diffs.astype(dt)
            # NULL inputs contribute nothing (SQL sum ignores NULLs; an
            # all-NULL group reads 0 until typed NULL aggregates land)
            accums.append(jnp.where(nv, jnp.zeros_like(contrib), contrib))
        else:
            raise NotImplementedError(f"accumulable agg {agg.func}")
    err = jnp.where(delta.live, err, 0)
    ok = delta.live & (err == 0)
    nrows = jnp.where(ok, delta.diffs, 0)
    accums = tuple(jnp.where(ok, a, jnp.zeros_like(a)) for a in accums)
    hashes = jnp.where(ok, hashes, PAD_HASH)
    err_mask = err != 0
    errs = UpdateBatch(
        hashes=jnp.where(err_mask, jnp.zeros_like(delta.hashes), PAD_HASH),
        keys=(),
        vals=(err.astype(I64_DTYPE),),
        times=jnp.where(err_mask, delta.times, PAD_TIME),
        diffs=jnp.where(err_mask, delta.diffs, 0),
    )
    return AccumState(hashes, keys, accums, nrows), errs


def lookup_accums(state: AccumState, probe: AccumState):
    """Gather state entries matching probe keys.

    Returns (found[bool], accums tuple, nrows, missed[bool]) aligned with
    probe rows. Scans up to _MAX_HASH_COLLISIONS slots of the probe's hash
    bucket; `missed` marks probes whose bucket is larger than the scan and
    that were not resolved within it — the lookup result for those rows is
    unsound and callers MUST surface an error rather than use it (the
    detect-and-error stance; silently treating the group as absent would be
    a wrong answer)."""
    return _lookup_accums(state, probe)


@jax.jit
def _lookup_accums(state: AccumState, probe: AccumState):
    lo = searchsorted(state.hashes, probe.hashes, side="left")
    hi = searchsorted(state.hashes, probe.hashes, side="right")
    from ..repr.hashing import value_view

    def scan(width: int):
        # unrolled Python loop, NOT fori_loop: `width` is static, so the
        # scan is `width` branchless gather/compare steps — no while loop in
        # the compiled tick, fully vectorized on XLA:CPU and the TPU VPU
        # (and no shard_map carry-varyingness pitfalls to manage).
        found = probe.live & False
        idx = lo * 0
        for off in range(width):
            cand = jnp.clip(lo + off, 0, state.cap - 1)
            eq = (lo + off) < hi
            for pk, sk in zip(probe.keys, state.keys):
                pv, sv = value_view(pk), value_view(sk)
                eq = eq & (pv == sv[cand])
            eq = eq & probe.live
            idx = jnp.where(eq & ~found, cand, idx)
            found = found | eq
        return found, idx

    found, idx = scan(_MAX_HASH_COLLISIONS)
    narrow_missed = jnp.any(
        probe.live & ~found & ((hi - lo) > _MAX_HASH_COLLISIONS)
    )
    # probe widening: the 64-slot re-scan traces into a lax.cond branch and
    # executes only on the (rare) tick where some bucket outgrew 4 slots
    found, idx = jax.lax.cond(
        narrow_missed,
        lambda: scan(_WIDE_HASH_COLLISIONS),
        lambda: (found, idx),
    )
    g = multi_take((*state.accums, state.nrows), idx)
    accums = tuple(jnp.where(found, a, 0) for a in g[:-1])
    nrows = jnp.where(found, g[-1], 0)
    missed = probe.live & ~found & ((hi - lo) > _WIDE_HASH_COLLISIONS)
    return found, accums, nrows, missed


# fixed-point float accumulators flag loudly before i64 wrap: 2^60 leaves
# 8x headroom over any single additional contribution (advisor r4: the
# engine's error model is loud failure, never silent mis-aggregation; the
# reference's Accum::Float carries i128 headroom instead)
_ACCUM_OVERFLOW_BOUND = 1 << 60


def accum_overflow_errs(
    contrib: AccumState, old_accums, aggs: tuple, time
) -> UpdateBatch | None:
    """Error rows for fixed-point accumulators near the i64 bound.

    Checks both the tick's contributions and the post-merge totals
    (old + contribution) of affected keys; returns None without touching
    the device when no agg is fixed-point (zero cost for integer
    aggregates)."""
    scales = tuple(getattr(a, "fixed_scale", 0) for a in aggs)
    if not any(scales):
        return None
    t = to_device_time(time)
    over = contrib.count() < 0  # varying-typed False
    for (c, o, s) in zip(contrib.accums, old_accums, scales):
        if not s:
            continue
        over = over | (jnp.abs(c) > _ACCUM_OVERFLOW_BOUND) | (
            jnp.abs(o + c) > _ACCUM_OVERFLOW_BOUND
        )
    over = over & contrib.live
    from ..expr.scalar import EvalErr

    code = jnp.asarray(int(EvalErr.NUMERIC_OVERFLOW), I64_DTYPE)
    return UpdateBatch(
        hashes=jnp.where(over, jnp.zeros_like(contrib.hashes), PAD_HASH),
        keys=(),
        vals=(jnp.where(over, code, 0),),
        times=jnp.where(over, t, PAD_TIME),
        diffs=jnp.where(over, 1, 0).astype(DIFF_DTYPE),
    )


@jax.jit
def collision_errs(probe: AccumState, missed, time, dup=None) -> UpdateBatch:
    """Error-collection rows for unresolved hash-bucket probes, and one more
    (in the first row) where `dup`, a table merge's double-collision flag
    (`merge_consolidate_accums`), is set."""
    from ..expr.scalar import EvalErr

    t = to_device_time(time)
    code = jnp.asarray(int(EvalErr.HASH_COLLISION_EXHAUSTED), I64_DTYPE)
    diffs = missed.astype(DIFF_DTYPE)
    if dup is not None:
        diffs = diffs.at[0].add(dup.astype(DIFF_DTYPE))
    err = diffs != 0
    return UpdateBatch(
        hashes=jnp.where(err, jnp.zeros_like(probe.hashes), PAD_HASH),
        keys=(),
        vals=(jnp.where(err, code, 0),),
        times=jnp.where(err, t, PAD_TIME),
        diffs=diffs,
    )


# A keyless reduce holds one group, so its output is at most that group's old
# row retracted and its new row inserted: it leaves a step at this capacity,
# whatever the capacity of the delta it stepped.
KEYLESS_OUT_CAP = bucket_cap(2)


@partial(jax.jit, static_argnames=("aggs", "keyless"))
def _emit_output(
    delta_keys: AccumState,
    old_accums,
    old_nrows,
    time: jnp.ndarray,
    aggs: tuple = (),
    keyless: bool = False,
) -> UpdateBatch:
    """Self-correcting output: -old aggregate row, +new aggregate row per key.

    delta_keys holds the *delta* contributions; new = old + delta. Output rows
    are (key cols ++ one col per aggregate), diff ±1 at `time`. With `aggs`,
    fixed-point float accumulators descale back to f32 output columns.
    `keyless` (a reduce with no key columns) emits from the first
    KEYLESS_OUT_CAP / 2 rows of a consolidated delta only: its one group is
    the first row, since consolidation puts live rows first.
    """
    if keyless:
        rows = min(KEYLESS_OUT_CAP // 2, delta_keys.cap)
        delta_keys = jax.tree_util.tree_map(lambda x: x[:rows], delta_keys)
        old_accums = tuple(a[:rows] for a in old_accums)
        old_nrows = old_nrows[:rows]
    live = delta_keys.live
    new_accums = tuple(o + d for o, d in zip(old_accums, delta_keys.accums))
    new_nrows = old_nrows + delta_keys.nrows
    scales = tuple(a.fixed_scale for a in aggs) if aggs else (0,) * len(new_accums)

    def descale(a, s):
        if not s:
            return a
        return a.astype(jnp.float32) / np.float32(1 << s)

    old_accums = tuple(descale(a, s) for a, s in zip(old_accums, scales))
    new_accums = tuple(descale(a, s) for a, s in zip(new_accums, scales))

    old_present = live & (old_nrows > 0)
    new_present = live & (new_nrows > 0)

    def interleave(a, b):
        return jnp.stack([a, b], axis=1).reshape(-1)

    hashes = interleave(
        jnp.where(old_present, delta_keys.hashes, PAD_HASH),
        jnp.where(new_present, delta_keys.hashes, PAD_HASH),
    )
    # output rows are raw (key cols ++ aggregate cols in vals); keys stay an
    # arrangement artifact and are left empty
    vals = tuple(interleave(k, k) for k in delta_keys.keys) + tuple(
        interleave(o, n) for o, n in zip(old_accums, new_accums)
    )
    t = to_device_time(time)
    times = interleave(
        jnp.where(old_present, t, PAD_TIME), jnp.where(new_present, t, PAD_TIME)
    )
    diffs = interleave(
        jnp.where(old_present, -1, 0).astype(DIFF_DTYPE),
        jnp.where(new_present, 1, 0).astype(DIFF_DTYPE),
    )
    return UpdateBatch(hashes, (), vals, times, diffs)


def step_counts(
    new_state: AccumState, contrib: AccumState, old_nrows, errs: UpdateBatch, dup
) -> jnp.ndarray:
    """i32[4] a reduce step hands the host in ONE read: the live groups of
    the table after the step, the groups whose output row changed in it
    (appeared, vanished, or present on both sides with an accumulator that
    moved; `contrib` is consolidated, so its live rows are the groups the
    tick touched, and one whose deltas cancel is not among them), the live
    rows of the error batch the step returns, and 1 where the table's merge
    flagged a double collision (`dup`), else 0."""
    was, now = old_nrows > 0, old_nrows + contrib.nrows > 0
    moved = was != was  # varying-typed False
    for d in contrib.accums:
        moved = moved | (d != 0)
    changed = contrib.live & ((was != now) | (was & now & moved))
    return jnp.stack(
        [
            new_state.count(),
            jnp.sum(changed.astype(jnp.int32)),
            errs.count(),
            dup.astype(jnp.int32),
        ]
    )


_step_counts = jax.jit(step_counts)


def read_step_counts(counts, errs: UpdateBatch):
    """The host's one read of `step_counts`: (live groups, changed groups,
    the step's error delta, or None where it holds no row, whether the
    table's merge flagged a collision). An empty error delta and none are
    the same collection, and None is what every operator downstream skips
    without a program."""
    groups, changed, n_errs, dup = (int(c) for c in np.asarray(counts))
    return groups, changed, errs if n_errs else None, bool(dup)


def accumulable_step(
    state: AccumState,
    delta: UpdateBatch,
    key_cols: tuple[int, ...],
    aggs: tuple[AggregateExpr, ...],
    time: int,
):
    """One tick of an accumulable reduce: (state, Δin, t) → (state', Δout, Δerrs, counts).

    Δout holds retractions of changed groups' old rows and insertions of
    their new rows, at time t (groups whose accumulators didn't change
    cancel), at twice cap(Δ), or at KEYLESS_OUT_CAP without key columns.
    Rows whose aggregate input expression errors land in Δerrs.
    The state comes back at cap(state) + cap(Δ), the consolidated delta
    merged into it (`merge_consolidate_accums`); callers cut it back to the
    bucket of its groups, which `counts` (`step_counts`, still on the
    device) hands them with the changed groups, Δerrs's live rows and the
    merge's collision flag in one read (`read_step_counts`). A double
    collision in the merge is an error row in Δerrs, never a wrong sum.
    """
    raw_contrib, errs = _contributions(delta, key_cols, aggs)
    contrib = consolidate_accums(raw_contrib)
    _found, old_accums, old_nrows, missed = lookup_accums(state, contrib)
    out = _emit_output(contrib, old_accums, old_nrows, time, aggs, keyless=not key_cols)
    from .consolidate import consolidate  # local import to avoid cycle

    out = consolidate(out)
    new_state, dup = merge_consolidate_accums(state, contrib)
    errs = consolidate(
        UpdateBatch.concat(errs, collision_errs(contrib, missed, time, dup))
    )
    ov = accum_overflow_errs(contrib, old_accums, aggs, time)
    if ov is not None:
        errs = consolidate(UpdateBatch.concat(errs, ov))
    counts = _step_counts(new_state, contrib, old_nrows, errs, dup)
    return new_state, out, errs, counts
