"""Fused renderer: ANY supported LIR plan → ONE jitted XLA program per tick.

The generalization of the hand-built Q3 model (models/fused_q3.py) to the
full LIR operator set: where the host-orchestrated runtime (runtime.py)
dispatches ~10 small kernels per operator per tick, this compiler walks a
`DataflowDescription` once and emits a single functional tick

    tick(state, source_deltas, time, since) -> (state', outs, errs, overflow)

that XLA compiles end to end — filters fuse into joins, intermediate batches
never round-trip to the host, and the only per-tick host work is padding the
input deltas and one tiny stats readback. This is the TPU answer to the
reference's `render_plan_expr` dispatcher (src/compute/src/render.rs:1155):
the reference renders operators into a timely graph scheduled at runtime; we
render them into one XLA program scheduled by the compiler.

All state is fixed-capacity (LSM levels, accumulator tables); overflow
flags replace resizing. The host driver (`FusedDataflow`) retries a tick
from the pre-tick state with doubled capacities when the flag trips, so
results are never lossy. Unsupported constructs (LetRec, TemporalFilter)
raise `FusedUnsupported`; callers fall back to the host-orchestrated path.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..arrangement.lsm import (
    LsmAccums,
    LsmBatches,
    accum_lsm_insert,
    accum_lsm_lookup,
    lsm_insert,
    lsm_join,
)
from ..arrangement.spine import Arrangement, arrange_batch
from ..ops.consolidate import advance_times, compact_to, consolidate
from ..ops.join import join_materialize, join_total
from ..ops.reduce import (
    AccumState,
    _contributions,
    _emit_output,
    consolidate_accums,
)
from ..ops.search import searchsorted
from ..ops.topk import _gather_materialize, distinct_keys, negate, topk_select
from ..repr.batch import (
    PAD_TIME,
    UpdateBatch,
    bucket_cap,
    device_time_scalar,
    to_device_time,
)
from . import plan as lir
from .runtime import ERR_DTYPES, materialize_counts

I64 = np.dtype(np.int64)

# error-stream compaction buffer: errors are almost always empty, so the
# concatenated per-operator error streams compact here before their
# canonicalizing sort (overflow of REAL error rows trips the tick retry)
_ERR_COMPACT_CAP = 8192


class FusedUnsupported(Exception):
    """Plan uses a construct the fused compiler does not render yet."""


@dataclass(frozen=True)
class FusedCaps:
    """Static capacities for one compiled dataflow (all powers of two).

    `scale` doubles every capacity at once — the overflow-retry knob.
    On a mesh these are PER-SHARD capacities; `bucket` is the per-destination
    exchange bucket (0 = auto: equal to `delta`, which is skew-proof for a
    delta-sized send).
    """

    delta: int = 1 << 10  # per-source per-tick delta rows
    arrangement: int = 1 << 14  # top LSM level per join/topk arrangement
    groups: int = 1 << 13  # top accumulator-table level per reduce
    join_out: int = 1 << 12  # join output cap (largest level; see join_caps)
    gather: int = 1 << 12  # topk gathered group contents per level
    bucket: int = 0  # exchange bucket per destination (0 = delta)
    levels: int = 3
    ratio: int = 8  # LSM merge-schedule ratio (lsm_merge_ratio dyncfg)
    cap_ratio: int = 4  # per-level join-output taper (fused_join_cap_ratio)

    def scaled(self, k: int) -> "FusedCaps":
        return FusedCaps(
            delta=self.delta * k,
            arrangement=self.arrangement * k,
            groups=self.groups * k,
            join_out=self.join_out * k,
            gather=self.gather * k,
            bucket=self.bucket * k,
            levels=self.levels,
            ratio=self.ratio,
            cap_ratio=self.cap_ratio,
        )

    def arr_levels(self, full: int) -> tuple:
        from ..models.fused_q3 import level_caps

        return level_caps(full, max(self.delta, 64), self.levels, ratio=self.ratio)

    def join_caps(self, probe_cap: int, arr_caps) -> tuple:
        """Per-LEVEL join output caps (the big-tick lever).

        A uniform (join_out,) × levels cap pays K × join_out concat/sort
        width per probe even though the small levels hold a ratio^k-th of
        the arrangement. Level i (small → large) gets
        join_out / cap_ratio^(levels-1-i), floored at the probe width (a
        fresh delta can match mostly-new rows sitting in level 0) and capped
        by the PROVABLE pair bound probe.cap × level.cap where that is
        tighter. cap_ratio=1 restores the uniform caps. Any taper stays
        lossless: a level whose matches exceed its cap trips the overflow
        retry like every other capacity in this file.
        """
        if hasattr(arr_caps, "levels"):
            arr_caps = tuple(b.cap for b in arr_caps.levels)
        n = len(arr_caps)
        ratio = max(int(self.cap_ratio), 1)  # dyncfg is unchecked; 0 would divide
        out = []
        for i, c in enumerate(arr_caps):
            cap = max(
                self.join_out // (ratio ** (n - 1 - i)),
                bucket_cap(probe_cap),
            )
            cap = min(cap, self.join_out, bucket_cap(probe_cap * c))
            out.append(max(cap, 8))
        return tuple(out)


# ---------------------------------------------------------------------------
# compilation
# ---------------------------------------------------------------------------


@dataclass
class _Ctx:
    """Per-trace context threaded through the emitted program."""

    state_in: dict
    state_out: dict
    env: dict  # source/object id -> UpdateBatch delta
    time: jnp.ndarray
    since: jnp.ndarray
    errs: list
    overflow: list
    memo: dict  # id(plan node) -> emitted UpdateBatch


class FusedCompiler:
    """Walks LIR plans; builds the state template and the traceable tick.

    With `axis_name` set (shard_map over a mesh axis), every batch headed for
    stateful-operator state is first exchanged to the shard owning its key
    hash (all_to_all riding ICI) — the timely worker-exchange pact placement
    (reference: src/timely-util/src/pact.rs): exchange before ArrangeBy-like
    state touch, never after stateless MFPs.
    """

    def __init__(
        self,
        desc: lir.DataflowDescription,
        caps: FusedCaps,
        axis_name: str | None = None,
        n_shards: int = 1,
    ):
        self.desc = desc
        self.caps = caps
        self.axis_name = axis_name
        self.n_shards = n_shards
        self.dtypes: dict[str, tuple] = {
            sid: tuple(dts) for sid, dts in desc.source_imports.items()
        }
        # state templates keyed by stable path id, built during a dry walk
        self.state_template: dict[str, object] = {}
        self._counter = 0
        self._emitters: dict = {}  # id(node) -> (emit_fn symbolic closure)
        for bd in desc.objects_to_build:
            self._check_supported(bd.plan)
            self.dtypes[bd.id] = tuple(bd.dtypes)
        # allocate state by walking plans once (deterministic order)
        self._alloc_memo: dict[int, str] = {}
        for bd in desc.objects_to_build:
            self._allocate(bd.plan, bd.id)

    # -- support check ------------------------------------------------------
    def _check_supported(self, e) -> None:
        if isinstance(e, (lir.LetRec, lir.TemporalFilter, lir.BasicAgg)):
            raise FusedUnsupported(type(e).__name__)
        from ..expr.scalar import expr_has_dictfunc

        if isinstance(e, lir.FlatMap):
            if e.func != "generate_series" or any(
                expr_has_dictfunc(x) for x in e.exprs
            ):
                raise FusedUnsupported("FlatMap")

        def no_dictfunc(exprs):
            # string-function tables are host state; they cannot bake into a
            # compiled tick (stale as the dictionary grows) — host path only
            if any(expr_has_dictfunc(x) for x in exprs):
                raise FusedUnsupported("DictFunc")

        if isinstance(e, lir.Mfp):
            no_dictfunc(list(e.mfp.map_exprs) + list(e.mfp.predicates))
        if isinstance(e, lir.Join) and e.closure is not None:
            no_dictfunc(list(e.closure.map_exprs) + list(e.closure.predicates))
        if isinstance(e, lir.Reduce) and not e.distinct:
            no_dictfunc([a.expr for a in e.aggs])
        for child in _children(e):
            self._check_supported(child)

    # -- dtype inference (mirrors runtime._infer_dtypes) --------------------
    def infer_dtypes(self, e) -> tuple:
        if isinstance(e, lir.Get):
            return self.dtypes[e.id]
        if isinstance(e, lir.Constant):
            return tuple(e.dtypes)
        if isinstance(e, lir.Mfp):
            from .runtime import _expr_dtype

            ins = self.infer_dtypes(e.input)
            cols = list(ins)
            for m in e.mfp.map_exprs:
                cols.append(_expr_dtype(m, cols))
            if e.mfp.projection is not None:
                cols = [cols[i] for i in e.mfp.projection]
            return tuple(cols)
        if isinstance(e, (lir.Negate, lir.Threshold, lir.ArrangeBy)):
            return self.infer_dtypes(e.input)
        if isinstance(e, lir.FlatMap):
            import numpy as _np

            return self.infer_dtypes(e.input) + (_np.dtype(_np.int64),)
        if isinstance(e, lir.Union):
            return self.infer_dtypes(e.inputs[0])
        if isinstance(e, lir.TopK):
            return self.infer_dtypes(e.input)
        if isinstance(e, lir.Reduce):
            ins = self.infer_dtypes(e.input)
            if e.distinct:
                return tuple(ins[i] for i in e.key_cols)
            from ..ops.reduce import agg_out_dtype

            return tuple(ins[i] for i in e.key_cols) + tuple(
                agg_out_dtype(a) for a in e.aggs
            )
        if isinstance(e, lir.Join):
            from .runtime import _expr_dtype

            cols = []
            for i in e.inputs:
                cols.extend(self.infer_dtypes(i))
            if e.closure is not None and e.closure.projection is not None:
                base = list(cols)
                for m in e.closure.map_exprs:
                    base.append(_expr_dtype(m, base))
                cols = [base[i] for i in e.closure.projection]
            return tuple(cols)
        raise FusedUnsupported(f"dtypes: {type(e).__name__}")

    # -- state allocation ---------------------------------------------------
    def _path(self, obj_id: str, kind: str) -> str:
        self._counter += 1
        return f"{obj_id}/{self._counter}:{kind}"

    def _allocate(self, e, obj_id: str) -> None:
        """Pre-build the state template for every stateful operator, in the
        same traversal order `_emit` uses (shared subtrees allocate once)."""
        if id(e) in self._alloc_memo:
            return
        self._alloc_memo[id(e)] = "visited"
        for child in _children(e):
            self._allocate(child, obj_id)
        caps = self.caps
        if isinstance(e, lir.Join):
            in_dts = [self.infer_dtypes(i) for i in e.inputs]
            if isinstance(e.plan, lir.LinearJoinPlan):
                slots = []
                for si, st in enumerate(e.plan.stages):
                    left_dts = _accum_dtypes_linear(in_dts, si)
                    lkd = tuple(left_dts[c] for c in st.stream_key)
                    rkd = tuple(in_dts[si + 1][c] for c in st.lookup_key)
                    lpath = self._path(obj_id, f"join{si}L")
                    rpath = self._path(obj_id, f"join{si}R")
                    self.state_template[lpath] = LsmBatches.empty(
                        caps.arr_levels(caps.arrangement), lkd, tuple(left_dts)
                    )
                    self.state_template[rpath] = LsmBatches.empty(
                        caps.arr_levels(caps.arrangement), rkd, tuple(in_dts[si + 1])
                    )
                    slots.append((lpath, rpath))
                self._emitters[id(e)] = ("linear_join", slots)
            else:
                arrs: dict = {}
                for path in e.plan.paths:
                    for st in path:
                        key = (st.other_input, st.lookup_key)
                        if key not in arrs:
                            dts = in_dts[st.other_input]
                            kd = tuple(dts[c] for c in st.lookup_key)
                            p = self._path(
                                obj_id, f"delta_in{st.other_input}"
                            )
                            self.state_template[p] = LsmBatches.empty(
                                caps.arr_levels(caps.arrangement), kd, tuple(dts)
                            )
                            arrs[key] = p
                self._emitters[id(e)] = ("delta_join", arrs)
        elif isinstance(e, lir.Reduce):
            in_dts = self.infer_dtypes(e.input)
            kd = tuple(in_dts[i] for i in e.key_cols)
            if e.distinct:
                p = self._path(obj_id, "distinct")
                self.state_template[p] = LsmAccums.empty(
                    caps.arr_levels(caps.groups), kd, ()
                )
            else:
                ad = tuple(np.dtype(a.accum_dtype) for a in e.aggs)
                p = self._path(obj_id, "reduce")
                self.state_template[p] = LsmAccums.empty(
                    caps.arr_levels(caps.groups), kd, ad
                )
            self._emitters[id(e)] = ("reduce", p)
        elif isinstance(e, lir.Threshold):
            in_dts = self.infer_dtypes(e.input)
            p = self._path(obj_id, "threshold")
            self.state_template[p] = LsmAccums.empty(
                caps.arr_levels(caps.groups), tuple(in_dts), ()
            )
            self._emitters[id(e)] = ("threshold", p)
        elif isinstance(e, lir.TopK):
            in_dts = self.infer_dtypes(e.input)
            kd = tuple(in_dts[i] for i in e.plan.group_cols)
            p = self._path(obj_id, "topk")
            self.state_template[p] = LsmBatches.empty(
                caps.arr_levels(caps.arrangement), kd, tuple(in_dts)
            )
            self._emitters[id(e)] = ("topk", p)

    # -- emission -----------------------------------------------------------
    def emit_tick(self, ctx: _Ctx) -> dict:
        """Trace every object build; returns {obj_id: oks batch}."""
        outs = {}
        for bd in self.desc.objects_to_build:
            out = self._emit(bd.plan, ctx)
            ctx.env[bd.id] = out
            outs[bd.id] = out
        return outs

    def _emit(self, e, ctx: _Ctx) -> UpdateBatch:
        hit = ctx.memo.get(id(e))
        if hit is not None:
            return hit
        from ..obs import profiler as _prof

        # named scope at TRACE time: HLO ops carry the plan-node name, so a
        # jax.profiler TPU trace attributes device time to operators; a
        # module-bool no-op when the profiler dyncfg is off
        with _prof.named_scope(f"mzt:{type(e).__name__}"):
            out = self._emit_new(e, ctx)
        ctx.memo[id(e)] = out
        return out

    def _emit_new(self, e, ctx: _Ctx) -> UpdateBatch:
        caps = self.caps
        if isinstance(e, lir.Get):
            return ctx.env[e.id]
        if isinstance(e, lir.Constant):
            # constants are injected by the host as pseudo-source deltas
            return ctx.env[_const_id(e)]
        if isinstance(e, lir.Mfp):
            inp = self._emit(e.input, ctx)
            if e.mfp.is_identity():
                return inp
            out, errs = e.mfp.apply(inp)
            ctx.errs.append(errs)
            return out
        if isinstance(e, lir.Negate):
            return negate(self._emit(e.input, ctx))
        if isinstance(e, lir.ArrangeBy):
            return self._emit(e.input, ctx)
        if isinstance(e, lir.Union):
            parts = [self._emit(i, ctx) for i in e.inputs]
            acc = parts[0]
            for p in parts[1:]:
                acc = UpdateBatch.concat(acc, p)
            return consolidate(acc)
        if isinstance(e, lir.FlatMap):
            # generate_series has a static fan-out bound (caps.join_out) with
            # an overflow flag — static shapes, so it fuses like a sized join
            from ..ops.flat_map import flat_map_materialize

            inp = self._emit(e.input, ctx)
            out, errs, over = flat_map_materialize(inp, e.exprs, caps.join_out)
            ctx.errs.append(errs)
            ctx.overflow.append(over)
            return out
        if isinstance(e, lir.Join):
            return self._emit_join(e, ctx)
        if isinstance(e, lir.Reduce):
            if e.distinct:
                return self._emit_multiplicity(
                    e, ctx, key_cols=e.key_cols, mode="distinct"
                )
            return self._emit_reduce(e, ctx)
        if isinstance(e, lir.Threshold):
            in_dts = self.infer_dtypes(e.input)
            return self._emit_multiplicity(
                e, ctx, key_cols=tuple(range(len(in_dts))), mode="threshold"
            )
        if isinstance(e, lir.TopK):
            return self._emit_topk(e, ctx)
        raise FusedUnsupported(type(e).__name__)

    def _union_outs(self, outs: list, out_cap: int, ctx: _Ctx) -> UpdateBatch:
        """Concat partials, O(n)-compact live rows, sort small, THEN shrink.

        The concatenation of K per-level join outputs is mostly padding;
        sorting it at full width was the mid-cap sort tail of the r5 profile.
        `compact_to` moves the live rows into one small
        buffer with a cumsum+scatter (no sort), so the canonicalizing sort
        runs at 2×out_cap instead of K× that. The 2× headroom exists because
        raw live rows are a MULTISET count: +/- pairs and duplicate rows from
        different join levels (normal under insert+delete churn) annihilate
        in the consolidate below, so compacting straight to out_cap would
        trip the retry flag on ticks whose consolidated output fits. Real
        overflow stays loud — compact_to flags live > 2×out_cap, and the
        final shrink checks the post-consolidation count exactly like the
        pre-compaction path did (a tripped flag aborts the tick; the host
        retries with doubled caps).

        With per-level join caps (FusedCaps.join_caps), the concat's total
        capacity is often PROVABLY below 2×out_cap already (sum of the
        tapered per-level caps bounds the live rows) — the `acc.cap >
        mid_cap` guard then skips the blanket 2× compaction pass outright
        and the canonicalizing sort runs at the tighter bound."""
        acc = outs[0]
        for p in outs[1:]:
            acc = UpdateBatch.concat(acc, p)
        mid_cap = 2 * out_cap
        if acc.cap > mid_cap:
            acc, over = compact_to(acc, mid_cap)
            ctx.overflow.append(over)
        merged = consolidate(acc)
        if merged.cap <= out_cap:
            return merged
        ctx.overflow.append(merged.count() > out_cap)
        return merged.with_capacity(out_cap)

    def _exchanged(self, keyed: UpdateBatch, ctx: _Ctx) -> UpdateBatch:
        """Route a keyed batch to the shard owning its hash (no-op off-mesh).

        Every stateful operator's input passes through here so co-keyed rows
        are co-located before probing/inserting sharded arrangements."""
        if self.axis_name is None:
            return keyed
        from ..parallel.devicemesh import exchange

        bucket = self.caps.bucket or self.caps.delta
        out, f = exchange(keyed, self.axis_name, self.n_shards, bucket)
        ctx.overflow.append(f)
        return consolidate(out, compact=False)

    def _emit_join(self, e: lir.Join, ctx: _Ctx) -> UpdateBatch:
        caps = self.caps
        kind, slots = self._emitters[id(e)]
        deltas = [self._emit(i, ctx) for i in e.inputs]
        if kind == "linear_join":
            stream = deltas[0]
            for si, st in enumerate(e.plan.stages):
                lpath, rpath = slots[si]
                L = ctx.state_in[lpath]
                R = ctx.state_in[rpath]
                dlk = self._exchanged(arrange_batch(stream, st.stream_key), ctx)
                drk = self._exchanged(
                    arrange_batch(deltas[si + 1], st.lookup_key), ctx
                )
                outs, f1 = lsm_join(dlk, R, caps.join_caps(dlk.cap, R))
                outs2, f2 = lsm_join(drk, L, caps.join_caps(drk.cap, L), swap=True)
                dd = join_materialize(dlk, drk, caps.join_out)
                fdd = join_total(dlk, drk) > caps.join_out
                ctx.overflow.extend([f1, f2, fdd])
                newL, f3 = lsm_insert(
                    L, dlk, ctx.time, caps.ratio, since=ctx.since
                )
                newR, f4 = lsm_insert(
                    R, drk, ctx.time, caps.ratio, since=ctx.since
                )
                ctx.overflow.extend([f3, f4])
                ctx.state_out[lpath] = newL
                ctx.state_out[rpath] = newR
                stream = self._union_outs(outs + outs2 + [dd], caps.join_out, ctx)
        else:  # delta join
            arrs = slots  # {(input, key): path}
            # current (start-of-tick) arrangements, updated as paths publish
            cur = {k: ctx.state_in[p] for k, p in arrs.items()}
            outs_all = []
            for k, path_stages in enumerate(e.plan.paths):
                stream = deltas[k]
                for st in path_stages:
                    probe = self._exchanged(
                        arrange_batch(stream, st.stream_key), ctx
                    )
                    lsm = cur[(st.other_input, st.lookup_key)]
                    parts, f = lsm_join(probe, lsm, caps.join_caps(probe.cap, lsm))
                    ctx.overflow.append(f)
                    stream = self._union_outs(parts, caps.join_out, ctx)
                outs_all.append(
                    _project_cols(stream, e.plan.permutations[k])
                )
                # publish input k's delta into its arrangements
                for (inp, key), path in arrs.items():
                    if inp == k:
                        keyed = self._exchanged(
                            arrange_batch(deltas[k], key), ctx
                        )
                        newA, f = lsm_insert(
                            cur[(inp, key)], keyed, ctx.time, caps.ratio,
                            since=ctx.since,
                        )
                        ctx.overflow.append(f)
                        cur[(inp, key)] = newA
                        ctx.state_out[path] = newA
            stream = self._union_outs(outs_all, caps.join_out, ctx)
        if e.closure is not None:
            stream, cerrs = e.closure.apply(stream)
            ctx.errs.append(cerrs)
        return stream

    def _emit_reduce(self, e: lir.Reduce, ctx: _Ctx) -> UpdateBatch:
        _kind, path = self._emitters[id(e)]
        lsm: LsmAccums = ctx.state_in[path]
        inp = self._emit(e.input, ctx)
        if self.axis_name is not None:
            inp = self._exchanged(arrange_batch(inp, e.key_cols), ctx)
        raw, errs = _contributions(inp, e.key_cols, e.aggs)
        ctx.errs.append(errs)
        contrib = consolidate_accums(raw)
        old_accums, old_nrows, missed = accum_lsm_lookup(lsm, contrib)
        from ..ops.reduce import accum_overflow_errs, collision_errs

        ctx.errs.append(collision_errs(contrib, missed, ctx.time))
        ov = accum_overflow_errs(contrib, old_accums, e.aggs, ctx.time)
        if ov is not None:
            ctx.errs.append(ov)
        out = consolidate(
            _emit_output(contrib, old_accums, old_nrows, ctx.time, e.aggs)
        )
        new_lsm, f = accum_lsm_insert(lsm, contrib, ctx.time, self.caps.ratio)
        ctx.overflow.append(f)
        ctx.state_out[path] = new_lsm
        return out

    def _emit_multiplicity(self, e, ctx: _Ctx, key_cols, mode: str) -> UpdateBatch:
        """Distinct / Threshold: multiplicity map over a per-row count table."""
        from ..ops.threshold import _multiplicity
        from ..repr.hashing import PAD_HASH

        _kind, path = self._emitters[id(e)]
        lsm: LsmAccums = ctx.state_in[path]
        inp = self._emit(e.input, ctx)
        if self.axis_name is not None:
            inp = self._exchanged(arrange_batch(inp, tuple(key_cols)), ctx)
        raw, _errs = _contributions(inp, tuple(key_cols), ())
        contrib = consolidate_accums(raw)
        _accs, old_n, missed = accum_lsm_lookup(lsm, contrib)
        from ..ops.reduce import collision_errs

        ctx.errs.append(collision_errs(contrib, missed, ctx.time))
        new_n = old_n + contrib.nrows
        out_d = _multiplicity(mode, new_n) - _multiplicity(mode, old_n)
        live = contrib.live & (out_d != 0)
        t = to_device_time(ctx.time)
        out = UpdateBatch(
            hashes=jnp.where(live, contrib.hashes, PAD_HASH),
            keys=(),
            vals=contrib.keys,
            times=jnp.where(live, t, PAD_TIME),
            diffs=jnp.where(live, out_d, 0),
        )
        new_lsm, f = accum_lsm_insert(lsm, contrib, ctx.time, self.caps.ratio)
        ctx.overflow.append(f)
        ctx.state_out[path] = new_lsm
        return consolidate(out)

    def _emit_topk(self, e: lir.TopK, ctx: _Ctx) -> UpdateBatch:
        caps = self.caps
        _kind, path = self._emitters[id(e)]
        lsm: LsmBatches = ctx.state_in[path]
        inp = self._emit(e.input, ctx)
        keyed = self._exchanged(arrange_batch(inp, e.plan.group_cols), ctx)
        probes = distinct_keys(keyed)
        old_rows, f1 = _gather_lsm(probes, lsm, caps.gather, ctx.time)
        new_lsm, f2 = lsm_insert(lsm, keyed, ctx.time, caps.ratio, since=ctx.since)
        new_rows, f3 = _gather_lsm(probes, new_lsm, caps.gather, ctx.time)
        ctx.overflow.extend([f1, f2, f3])
        ctx.state_out[path] = new_lsm
        old_top = topk_select(
            old_rows, e.plan.order_by, e.plan.limit, e.plan.offset, ctx.time,
            e.plan.nulls_last,
        )
        new_top = topk_select(
            new_rows, e.plan.order_by, e.plan.limit, e.plan.offset, ctx.time,
            e.plan.nulls_last,
        )
        return consolidate(UpdateBatch.concat(new_top, negate(old_top)))


def _gather_lsm(probes: UpdateBatch, lsm: LsmBatches, cap: int, time):
    """Gather every arrangement row matching a probe key, across levels.

    Per-level overflow (what `_gather_materialize` can actually drop) trips
    the retry flag."""
    parts = []
    overflow = jnp.asarray(False)
    for level in lsm.levels:
        lo = searchsorted(level.hashes, probes.hashes, side="left")
        hi = searchsorted(level.hashes, probes.hashes, side="right")
        overflow = overflow | (
            jnp.sum(jnp.where(probes.live, hi - lo, 0)) > cap
        )
        parts.append(_gather_materialize(probes, level, cap))
    acc = parts[0]
    for p in parts[1:]:
        acc = UpdateBatch.concat(acc, p)
    return consolidate(advance_times(acc, time)), overflow


def _project_cols(batch: UpdateBatch, perm) -> UpdateBatch:
    return UpdateBatch(
        batch.hashes, (), tuple(batch.vals[i] for i in perm), batch.times, batch.diffs
    )


def _grow_rows(have, want_cap: int, n_shards: int):
    """Grow a level (UpdateBatch or AccumState) to `want_cap` total rows,
    padding each of the n per-shard slices at its own tail."""
    if have.cap == want_cap:
        return have
    if n_shards == 1:
        return have.with_capacity(want_cap)
    per_have = have.cap // n_shards
    per_want = want_cap // n_shards
    kind = type(have)
    shards = [
        jax.tree_util.tree_map(
            lambda a, i=i: a[i * per_have : (i + 1) * per_have], have
        ).with_capacity(per_want)
        for i in range(n_shards)
    ]
    acc = shards[0]
    for s in shards[1:]:
        acc = kind.concat(acc, s)
    return acc


def _accum_dtypes_linear(in_dts: list, stage_i: int) -> list:
    """Column dtypes of the accumulated stream entering stage i."""
    cols: list = []
    for k in range(stage_i + 1):
        cols.extend(in_dts[k])
    return cols


def _children(e):
    if isinstance(
        e, (lir.Mfp, lir.Negate, lir.Threshold, lir.ArrangeBy, lir.TopK, lir.BasicAgg)
    ):
        return (e.input,)
    if isinstance(e, lir.Reduce):
        return (e.input,)
    if isinstance(e, (lir.Union, lir.Join)):
        return tuple(e.inputs)
    if isinstance(e, lir.TemporalFilter):
        return (e.input,)
    if isinstance(e, lir.FlatMap):
        return (e.input,)
    if isinstance(e, lir.LetRec):
        return tuple(b[1] for b in e.bindings) + (e.body,)
    return ()


def _const_id(e: lir.Constant) -> str:
    return f"__const_{id(e)}"


def _collect_constants(e, acc: dict) -> None:
    if isinstance(e, lir.Constant):
        acc[_const_id(e)] = e
    for c in _children(e):
        _collect_constants(c, acc)


# ---------------------------------------------------------------------------
# host driver
# ---------------------------------------------------------------------------


class FusedDataflow:
    """Drop-in alternative to runtime.Dataflow for supported plans.

    Same host interface (`step`, `peek`, `compact`, `frontier`), but the
    whole tick is one jitted program. Overflow retries re-run the SAME tick
    from the pre-tick state with doubled capacities (lossless by design).

    With `mesh`, the tick runs under shard_map over `axis_name`: every
    arrangement and accumulator table is hash-sharded across the mesh
    (state arrays carry n_shards× the per-shard capacity on axis 0) and
    keyed streams are exchanged to their hash owner before every stateful
    operator — the SQL engine's multi-worker execution mode, replacing the
    reference's intra-replica timely worker sharding
    (src/cluster/src/communication.rs:100) with XLA collectives over ICI.
    """

    def __init__(
        self,
        desc: lir.DataflowDescription,
        caps: Optional[FusedCaps] = None,
        mesh=None,
        axis_name: str = "workers",
        traces=None,
        operator_logging: bool = False,
    ):
        # `traces`: the host TraceManager, when arrangement sharing is on.
        # Fused state is device-resident and cannot import a host spine, so
        # a plan whose stateful operators would IMPORT an existing shared
        # trace yields to the host renderer (which gets the sharing win);
        # with no importable trace the fused render proceeds privately —
        # it simply doesn't export, and later host dataflows export their
        # own (the FusedUnsupported-without-breaking-the-fallback contract).
        if traces is not None:
            from ..arrangement.trace_manager import shared_trace_keys

            if any(k in traces.traces for k in shared_trace_keys(desc)):
                raise FusedUnsupported("shared-trace import (host-resident spine)")
        self.desc = desc
        self.caps = caps or FusedCaps()
        self.mesh = mesh
        self.axis_name = axis_name
        self.n_shards = int(mesh.shape[axis_name]) if mesh is not None else 1
        self._scale = 1
        self._build()
        self.state = self._on_mesh(self._tiled_template())
        self.index_traces: dict[str, Arrangement] = {}
        self.index_errs: dict[str, Arrangement] = {}
        for idx_id, (obj_id, key_cols) in desc.index_exports.items():
            self.index_traces[idx_id] = Arrangement(key_cols=tuple(key_cols))
            self.index_errs[idx_id] = Arrangement(key_cols=())
        self.sink_outputs: dict[str, list] = {s: [] for s in desc.sink_exports}
        self.frontier = desc.as_of
        self.has_temporal = False
        self.since = 0
        self._emitted_consts: set[str] = set()
        self.metrics: dict = {}
        self.operator_logging = operator_logging
        # the whole tick is one program, so instrumentation is per-dataflow:
        # elapsed/invocations always on, row counts gated, and `retries`
        # counts overflow-ladder escalations (mz_dataflow_operator_rates)
        self.retries = 0
        self._elapsed_ns = 0
        self._invocations = 0
        self._rows_in = 0
        self._rows_out = 0
        self._profile_name = next(
            iter(desc.index_exports),
            next(iter(b.id for b in desc.objects_to_build), "fused"),
        )

    # -- compile ------------------------------------------------------------
    def _build(self) -> None:
        axis = self.axis_name if self.mesh is not None else None
        self.compiler = FusedCompiler(
            self.desc,
            self.caps.scaled(self._scale),
            axis_name=axis,
            n_shards=self.n_shards,
        )
        self.consts: dict[str, lir.Constant] = {}
        for bd in self.desc.objects_to_build:
            _collect_constants(bd.plan, self.consts)
        self.source_ids = list(self.desc.source_imports) + list(self.consts)

        def tick(state, deltas, time, since):
            ctx = _Ctx(
                state_in=state,
                state_out=dict(state),
                env=dict(deltas),
                time=time,
                since=since,
                errs=[],
                overflow=[jnp.asarray(False)],
                memo={},
            )
            outs = self.compiler.emit_tick(ctx)
            if ctx.errs:
                # error streams are almost always empty: O(n)-compact the
                # concat into a small buffer before the canonicalizing sort;
                # an overflow of real error rows trips the retry flag (loud,
                # never silently dropped). The cap scales with the retry
                # ladder: error-row count is data-dependent (doubling the
                # operator caps can't shrink it), so a fixed cap would make
                # a >cap error burst retry forever.
                err_cap = _ERR_COMPACT_CAP * self._scale
                errs = ctx.errs[0]
                for p in ctx.errs[1:]:
                    errs = UpdateBatch.concat(errs, p)
                if errs.cap > err_cap:
                    errs, err_over = compact_to(errs, err_cap)
                    ctx.overflow.append(err_over)
                errs = consolidate(errs)
            else:
                errs = UpdateBatch.empty(8, (), ERR_DTYPES)
            over = jnp.stack([jnp.asarray(f).reshape(()) for f in ctx.overflow])
            counts = jnp.stack(
                [outs[bd.id].count() for bd in self.desc.objects_to_build]
                + [errs.count()]
            )
            # shape (1,)/(1,k) so shard_map concatenates per-device results
            return (
                ctx.state_out,
                outs,
                errs,
                jnp.any(over).reshape((1,)),
                counts.reshape((1, -1)),
            )

        if self.mesh is None:
            self._tick = jax.jit(tick)
        else:
            from jax.sharding import PartitionSpec as P

            from ..parallel.devicemesh import mesh_jit

            spec, rep = P(self.axis_name), P()
            self._tick = mesh_jit(
                tick,
                self.mesh,
                in_specs=(spec, spec, rep, rep),
                out_specs=(spec, spec, spec, spec, spec),
                axis_name=self.axis_name,
            )

    def _tiled_template(self) -> dict:
        """State at GLOBAL shape: per-shard template tiled n_shards× on axis 0
        (shard_map splits it evenly, giving each shard its per-shard slice)."""
        tmpl = dict(self.compiler.state_template)
        if self.n_shards == 1:
            return tmpl
        n = self.n_shards
        return jax.tree_util.tree_map(
            lambda x: jnp.concatenate([x] * n, axis=0), tmpl
        )

    def _on_mesh(self, state: dict) -> dict:
        """State the host built, placed as a tick's output is placed: sharded
        on axis 0 over the mesh. The sharding is part of an array's type, so
        without this the hydration call (host-placed state) and every later
        call (the previous tick's output) trace and compile the whole tick
        program twice."""
        if self.mesh is None:
            return state
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        return jax.device_put(state, NamedSharding(self.mesh, P(self.axis_name)))

    def ensure_delta_capacity(self, n_rows: int) -> None:
        """Grow capacities (and recompile + migrate state) until a tick of
        `n_rows` input rows fits. Used for bulk hydration ticks and oversized
        inputs, avoiding the overflow-retry ladder."""
        if self._delta_cap() >= max(n_rows, 1):
            return
        while self._delta_cap() < n_rows:
            self._scale *= 2
        self.retries += 1
        self._build()
        self._migrate_state()

    def _migrate_state(self) -> None:
        """Pad existing state into the new (larger) capacity template.

        On a mesh, growth must happen PER SHARD: each shard's slice pads at
        its own tail, so live rows keep their owning shard after the resize
        (a global tail-pad would shift every shard boundary)."""
        tmpl = self._tiled_template()
        new_state = {}
        for path, t in tmpl.items():
            cur = self.state.get(path)
            if cur is None:
                new_state[path] = t
                continue
            new_levels = tuple(
                _grow_rows(have, want.cap, self.n_shards)
                for have, want in zip(cur.levels, t.levels)
            )
            new_state[path] = type(t)(new_levels)
        self.state = self._on_mesh(new_state)

    def _delta_cap(self) -> int:
        """GLOBAL per-source delta capacity (n_shards × the per-shard cap)."""
        return self.caps.scaled(self._scale).delta * self.n_shards

    # -- drive --------------------------------------------------------------
    def step(self, tick: int, source_deltas: dict[str, UpdateBatch]) -> dict:
        import time as _time

        from ..obs import profiler as _prof

        t0 = _time.perf_counter_ns()
        delta_cap = self._delta_cap()
        deltas: dict[str, UpdateBatch] = {}
        rows_in = 0
        for sid, dts in self.desc.source_imports.items():
            b = source_deltas.get(sid)
            if b is None:
                deltas[sid] = UpdateBatch.empty(delta_cap, (), tuple(dts))
            else:
                n = int(b.count())
                rows_in += n
                if n > delta_cap:
                    # oversized input tick: grow + recompile before trying
                    self.ensure_delta_capacity(n)
                    return self.step(tick, source_deltas)
                deltas[sid] = b.with_capacity(delta_cap)
        for cid, c in self.consts.items():
            deltas[cid] = self._const_delta(cid, c, tick, delta_cap)

        with _prof.annotate(f"mzt_fused_tick:{self._profile_name}"):
            # stage the time scalars on device EAGERLY: inside the jitted call
            # a bare np.uint32 is an implicit host→device transfer, which the
            # transfer_guard("disallow") differentials (conftest
            # device_tick_guard) rightly reject
            t_dev = jnp.asarray(device_time_scalar(tick))
            s_dev = jnp.asarray(device_time_scalar(self.since))
            state2, outs, errs, over, counts = self._tick(
                self.state, deltas, t_dev, s_dev
            )
        if bool(np.asarray(over).any()):
            # lossless retry: drop results, double capacities, re-run the
            # same tick from the unchanged pre-tick state
            if self.mesh is not None:
                from ..parallel.devicemesh import note_overflow_retry

                note_overflow_retry()
            self.retries += 1
            self._elapsed_ns += _time.perf_counter_ns() - t0
            self._scale *= 2
            self._build()
            self._migrate_state()
            return self.step(tick, source_deltas)
        self.state = state2
        counts = np.asarray(counts).sum(axis=0)  # (shards, k) -> (k,)
        # mark constants emitted only after a successful tick
        for cid, c in self.consts.items():
            if all(r[1] <= tick for r in c.rows):
                self._emitted_consts.add(cid)

        results: dict = {}
        err_delta = errs if int(counts[-1]) > 0 else None
        for i, bd in enumerate(self.desc.objects_to_build):
            oks = outs[bd.id] if int(counts[i]) > 0 else None
            results[bd.id] = (
                None if (oks is None and err_delta is None) else (oks, err_delta)
            )
        for idx_id, (obj_id, _k) in self.desc.index_exports.items():
            d = results.get(obj_id)
            if d is not None:
                oks, ie = d
                if oks is not None:
                    self.index_traces[idx_id].insert(oks)
                if ie is not None:
                    self.index_errs[idx_id].insert(ie)
        for sink_id, obj_id in self.desc.sink_exports.items():
            d = results.get(obj_id)
            if d is not None and d[0] is not None:
                self.sink_outputs[sink_id].append((tick, d[0]))
        self._elapsed_ns += _time.perf_counter_ns() - t0
        self._invocations += 1
        if self.operator_logging:
            self._rows_in += rows_in
            self._rows_out += int(counts[:-1].sum())
        self.frontier = tick + 1
        return results

    def _const_delta(
        self, cid: str, c: lir.Constant, tick: int, delta_cap: int
    ) -> UpdateBatch:
        if cid in self._emitted_consts:
            return UpdateBatch.empty(delta_cap, (), tuple(c.dtypes))
        pending = [r for r in c.rows if r[1] <= tick]
        if not pending:
            return UpdateBatch.empty(delta_cap, (), tuple(c.dtypes))
        cols = tuple(
            np.array([r[0][i] for r in pending], dtype=c.dtypes[i])
            for i in range(len(c.dtypes))
        )
        times = np.array([max(r[1], tick) for r in pending], dtype=np.uint64)
        diffs = np.array([r[2] for r in pending], dtype=np.int64)
        return UpdateBatch.build((), cols, times, diffs, cap=delta_cap)

    # -- reads / maintenance (same surface as runtime.Dataflow) -------------
    def peek(
        self,
        index_id: str,
        at: Optional[int] = None,
        byte_budget: int | None = None,
    ) -> list[tuple]:
        at = self.frontier - 1 if at is None else at
        acc: dict[tuple, int] = {}
        for data, _t, d in self.index_errs[index_id].rows_host(at):
            acc[data] = acc.get(data, 0) + d
        if any(v > 0 for v in acc.values()):
            from .runtime import peek_error_message

            raise RuntimeError(peek_error_message(index_id, acc))
        out: dict[tuple, int] = {}
        for data, _t, d in self.index_traces[index_id].rows_host(at):
            out[data] = out.get(data, 0) + d
        return materialize_counts(out, index_id, byte_budget=byte_budget)

    def compact(self, since: int) -> None:
        self.since = max(self.since, since)
        for arr in self.index_traces.values():
            arr.compact(since)
        for arr in self.index_errs.values():
            arr.compact(since)

    def operator_info(self) -> list:
        # one fused program per tick: a single pseudo-operator carries the
        # whole dataflow's elapsed/invocations (same 5-tuple shape as the
        # host renderer's per-operator rows)
        return [("fused", 0, "FusedTick", self._elapsed_ns, self._invocations)]

    def operator_rates(self) -> list:
        return [
            ("fused", 0, "FusedTick", self._rows_in, self._rows_out, self.retries)
        ]

    def arrangement_info(self) -> list:
        from .runtime import accum_state_nbytes, arrangement_nbytes, batch_nbytes

        def _leaves_nbytes(st):
            if isinstance(st, LsmBatches):
                return sum(batch_nbytes(b) for b in st.levels)
            return sum(accum_state_nbytes(a) for a in st.levels)

        out = []
        for path, st in self.state.items():
            if isinstance(st, LsmBatches):
                n = sum(int(b.count()) for b in st.levels)
                cap = sum(b.cap for b in st.levels)
            else:
                n = sum(int(a.count()) for a in st.levels)
                cap = sum(a.cap for a in st.levels)
            out.append(("fused", 0, path, len(st.levels), cap, n, _leaves_nbytes(st)))
        for idx_id, arr in self.index_traces.items():
            out.append(
                (
                    idx_id,
                    -1,
                    "index_trace",
                    len(arr.batches),
                    arr.total_cap(),
                    int(arr.count()),
                    arrangement_nbytes(arr),
                )
            )
        for idx_id, arr in self.index_errs.items():
            out.append(
                (
                    idx_id,
                    -1,
                    "index_errs",
                    len(arr.batches),
                    arr.total_cap(),
                    int(arr.count()),
                    arrangement_nbytes(arr),
                )
            )
        return out
