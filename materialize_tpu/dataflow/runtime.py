"""Render LIR plans into stateful operators and drive them tick by tick.

The host-side analogue of the reference's render + compute_state machinery
(src/compute/src/render.rs:202 `build_compute_dataflow`,
render.rs:1155 `render_plan_expr`, compute_state.rs:86): the control plane —
operator graph, frontier bookkeeping, state capacity management — lives here
in Python; every batch of actual data work is a jitted XLA program from
materialize_tpu.ops.

Per tick, every collection produces an optional delta `(oks, errs)`; `None`
means "no change", which lets quiet subgraphs skip kernel dispatch entirely
(the analogue of timely operators not being scheduled without capabilities).
Both oks and errs follow the twin-collection error design of
src/compute/src/render.rs:30-101.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..arrangement.spine import HEAD_RATIO, Arrangement, arrange_batch, sized
from ..obs import log as obs_log
from ..obs import metrics as obs_metrics
from ..obs.spans import TRACER
from ..ops.consolidate import consolidate, gather_parts
from ..ops.join import join_against, join_totals
from ..ops.reduce import AccumState, accumulable_step, agg_out_dtype, read_step_counts
from ..ops.threshold import threshold_step
from ..ops.topk import negate as negate_batch
from ..ops.topk import topk_step
from ..repr.batch import MIN_CAP, UpdateBatch, bucket_cap
from . import plan as lir

_log = obs_log.get_logger("render")

ERR_DTYPES = (np.dtype(np.int64),)

Delta = Optional[tuple[Optional[UpdateBatch], Optional[UpdateBatch]]]


class ShardContext:
    """One worker's view of a sharded dataflow (cluster/mesh.py data plane).

    When a replica runs as N processes × W workers, every worker renders the
    SAME DataflowDescription with a ShardContext; channel ids are allocated
    in render order, so identical rendering on every worker yields identical
    channel numbering — the deterministic-channel discipline of timely's
    exchange pact allocation. `exchange` is the network-boundary analogue of
    parallel/devicemesh/exchange.py's device all_to_all: host-staged, hash-partitioned
    by the routing columns' values (parallel/netexchange.py), delivered over
    the epoch-fenced WorkerMesh.
    """

    def __init__(self, mesh, dataflow_id: str, worker: int, n_workers: int):
        self.mesh = mesh
        self.dataflow_id = dataflow_id
        self.worker = worker
        self.n_workers = n_workers
        self._next_channel = 0
        # per-TICK exchange deadline (set by Dataflow.step via begin_tick):
        # all of a tick's exchanges share one budget, so a tick with many
        # channels can't stretch a stall to channels × per-exchange timeout
        self._tick_deadline: Optional[float] = None

    def alloc_channel(self):
        c = self._next_channel
        self._next_channel += 1
        return (self.dataflow_id, c)

    def begin_tick(self, tick: int) -> None:
        import time as _time

        budget = getattr(self.mesh, "exchange_timeout", 300.0)
        self._tick_deadline = _time.perf_counter() + budget

    def exchange(
        self, channel, tick: int, batch: Optional[UpdateBatch], key_cols
    ) -> Optional[UpdateBatch]:
        """Route `batch`'s live rows by hash of `key_cols` (None = whole row,
        () = keyless → worker 0); blocks until every peer's part for this
        (channel, tick) arrived — the per-channel progress accounting that
        makes closing a timestamp safe. A stall past the tick's shared
        deadline raises MeshError (the controller then reforms the mesh)."""
        import time as _time

        from ..parallel.netexchange import merge_parts, partition_batch

        parts = partition_batch(batch, key_cols, self.n_workers)
        timeout = None
        if self._tick_deadline is not None:
            timeout = max(0.05, self._tick_deadline - _time.perf_counter())
        received = self.mesh.exchange(
            self.worker, channel, tick, parts, timeout=timeout
        )
        return merge_parts(received)


def _union(parts: list[UpdateBatch]) -> Optional[UpdateBatch]:
    parts = [p for p in parts if p is not None]
    if not parts:
        return None
    acc = parts[0]
    for p in parts[1:]:
        acc = UpdateBatch.concat(acc, p)
    return consolidate(acc)


def _probe(probe: UpdateBatch, batches: list, swap: bool = False) -> list:
    """`join_against` for a linear join's stage: matches that fit a
    HEAD_RATIO-th of the probe's capacity in all come back at that one size.
    A delta's bucket repeats from tick to tick (an arrangement's head is
    built on that), the few rows a selective stage leaves of it do not. A
    stage that matched nothing hands on nothing (a keyless reduce's default
    row is such a stage in most ticks, and its view's delta would widen)."""
    totals = join_totals(probe, batches)
    if not sum(totals):
        return []
    return join_against(probe, batches, swap, floor=bucket_cap(probe.cap // HEAD_RATIO), totals=totals)


def _project(batch: UpdateBatch, cols: tuple[int, ...]) -> UpdateBatch:
    return UpdateBatch(
        batch.hashes, (), tuple(batch.vals[i] for i in cols), batch.times, batch.diffs
    )


class Node:
    """One rendered LIR operator."""

    def step(self, tick: int, ins: list[Delta]) -> Delta:
        raise NotImplementedError

    def compact(self, since: int) -> None:
        pass

    def state_info(self) -> list:
        """Introspection: [(arrangement name, n_batches, capacity, records)].

        The analogue of the reference's mz_arrangement_sizes logging
        (src/compute/src/logging, doc/developer/arrangements.md:34).
        """
        return []


class ExchangeNode(Node):
    """Cross-worker exchange pact in front of a stateful operator.

    Participates in the shuffle EVERY tick — even with no local input, peers
    may be sending rows this worker owns, and the punctuation (empty part)
    this worker contributes is what lets peers close the timestamp. Errors
    stay local: the error collection is a union across workers at peek time.
    """

    def __init__(self, shard: ShardContext, channel, key_cols):
        self.shard = shard
        self.channel = channel
        self.key_cols = key_cols

    def step(self, tick, ins):
        d = ins[0]
        oks = d[0] if d is not None else None
        errs = d[1] if d is not None else None
        out = self.shard.exchange(self.channel, tick, oks, self.key_cols)
        if out is None and errs is None:
            return None
        return out, errs


class ConstantNode(Node):
    def __init__(self, expr: lir.Constant, emit: bool = True):
        self.rows = expr.rows if emit else ()
        self.dtypes = expr.dtypes
        self.emitted = not emit

    def step(self, tick, ins):
        if self.emitted:
            return None
        pending = [r for r in self.rows if r[1] <= tick]
        if not pending:
            return None
        self.emitted = all(r[1] <= tick for r in self.rows)
        cols = tuple(
            np.array([r[0][i] for r in pending], dtype=self.dtypes[i])
            for i in range(len(self.dtypes))
        )
        times = np.array([max(r[1], tick) for r in pending], dtype=np.uint64)
        diffs = np.array([r[2] for r in pending], dtype=np.int64)
        return UpdateBatch.build((), cols, times, diffs), None


class MfpNode(Node):
    def __init__(self, mfp):
        self.mfp = mfp

    def step(self, tick, ins):
        d = ins[0]
        if d is None:
            return None
        oks, errs = d
        if oks is None:
            return None if errs is None else (None, errs)
        if self.mfp.is_identity():
            return oks, errs
        out, new_errs = self.mfp.apply(oks)
        return out, _union([errs, new_errs])


class FlatMapNode(Node):
    """generate_series fan-out via the two-pass sized kernel (ops/flat_map.py);
    output capacity follows the count pass (pow2-bucketed)."""

    def __init__(self, expr):
        self.exprs = tuple(expr.exprs)

    def step(self, tick, ins):
        from ..ops.flat_map import flat_map_materialize, flat_map_total

        d = ins[0]
        if d is None:
            return None
        oks, errs = d
        if oks is None:
            return None if errs is None else (None, errs)
        total = int(flat_map_total(oks, self.exprs))
        out, new_errs, _over = flat_map_materialize(
            oks, self.exprs, bucket_cap(total)
        )
        return out, _union([errs, new_errs])


class NegateNode(Node):
    def step(self, tick, ins):
        d = ins[0]
        if d is None:
            return None
        oks, errs = d
        return (negate_batch(oks) if oks is not None else None), errs


class UnionNode(Node):
    def step(self, tick, ins):
        oks = _union([d[0] for d in ins if d is not None])
        errs = _union([d[1] for d in ins if d is not None])
        if oks is None and errs is None:
            return None
        return oks, errs


class ArrangeByNode(Node):
    def __init__(self, key_cols: tuple[int, ...]):
        self.arr = Arrangement(key_cols=key_cols)

    def step(self, tick, ins):
        d = ins[0]
        if d is None:
            return None
        oks, errs = d
        if oks is not None:
            self.arr.insert(oks)
        return oks, errs

    def compact(self, since):
        self.arr.compact(since)

    def state_info(self):
        return self.arr.info_rows("arrange_by")


def _shared_state_info(h) -> tuple:
    """(batches, cap, records) to REPORT for a shared trace handle: the
    exporter owns the memory; importers report zero cap/records so summing
    mz_arrangement_sizes across dataflows counts every shared trace once."""
    nb, cap, rec = h.trace.state_info()
    if h.imported:
        return nb, 0, 0
    return nb, cap, rec


# -- arrangement byte accounting (the id-deduped scheme shared with
#    benchmarks/bench_shared_mvs.py: owners charge, importers report zero) ---


def batch_nbytes(b) -> int:
    n = 0
    for attr in ("hashes", "times", "diffs"):
        v = getattr(b, attr, None)
        if v is not None:
            n += int(getattr(v, "nbytes", 0))
    for attr in ("keys", "vals"):
        for col in getattr(b, attr, ()) or ():
            n += int(getattr(col, "nbytes", 0))
    return n


def arrangement_nbytes(arr) -> int:
    return sum(batch_nbytes(b) for b in arr.batches)


def _arr_row_nbytes(arr) -> list:
    """Bytes aligned with `Arrangement.info_rows`: the spine, then the head."""
    if not arr.head_bound:
        return [arrangement_nbytes(arr)]
    head = batch_nbytes(arr.head)
    return [arrangement_nbytes(arr) - head, head]


def accum_state_nbytes(st) -> int:
    n = 0
    for attr in ("hashes", "times"):
        v = getattr(st, attr, None)
        if v is not None:
            n += int(getattr(v, "nbytes", 0))
    for attr in ("keys", "accums", "vals"):
        for col in getattr(st, attr, ()) or ():
            n += int(getattr(col, "nbytes", 0))
    return n


def _shared_handle_nbytes(h) -> int:
    """Bytes to report for a shared trace handle: importers 0 (the exporter
    owns the memory), exporters the trace's arrangement (SharedTrace) or
    accumulator + output arrangement (SharedReduceTrace)."""
    if h.imported:
        return 0
    tr = h.trace
    arr = getattr(tr, "arr", None)
    if arr is not None:
        return arrangement_nbytes(arr)
    return accum_state_nbytes(tr.state) + arrangement_nbytes(tr.out_arr)


class SharedArrangeNode(Node):
    """ArrangeBy over a shared trace: pass the delta through, offering it to
    the trace (one LSM insert per tick TOTAL across every reader — the
    arrangement-sharing contract) instead of maintaining a private spine."""

    def __init__(self, handle, key_cols: tuple[int, ...]):
        self.h = handle
        self.key_cols = key_cols

    def step(self, tick, ins):
        d = ins[0]
        if d is None:
            return None
        oks, errs = d
        if oks is not None:
            self.h.offer(tick, arrange_batch(oks, self.key_cols))
        return oks, errs

    def state_info(self):
        return [(self.h.name(),) + _shared_state_info(self.h)]


class LinearJoinNode(Node):
    """Binary join chain; each stage keeps arrangements of both sides
    (the differential `join_core` shape, linear_join.rs).

    `shared` (one (stream handle, lookup handle) pair per stage, entries
    None where private) swaps a side's private arrangement for a shared
    trace: the tick's delta is OFFERED up front (so `thru(t)` includes it)
    and probes pick the time-consistent view — dA joins the other side
    THROUGH t, dB joins this side BEFORE t, and the dA⋈dB term is emitted
    only when the right side is private (a shared right's thru(t) probe
    already covers it). Stream-side sharing only applies to stage 0, whose
    stream is an imported collection; later stages accumulate dataflow-
    private intermediates."""

    def __init__(self, jplan: lir.LinearJoinPlan, closure, shard=None, shared=None):
        self.stages = jplan.stages
        self.closure = closure
        self.shard = shard
        self.shared = shared or [(None, None) for _ in self.stages]
        # sharded: both sides of every stage exchange by the stage's join key
        # before touching state, so matching rows co-locate (the pact.rs
        # key-hash discipline at the process boundary). Channel allocation
        # happens here, in render order — identical on every worker.
        self.channels = (
            [(shard.alloc_channel(), shard.alloc_channel()) for _ in self.stages]
            if shard is not None
            else None
        )
        self.state: list[tuple] = [
            (
                None if lh is not None else Arrangement(key_cols=s.stream_key),
                None if rh is not None else Arrangement(key_cols=s.lookup_key),
            )
            for s, (lh, rh) in zip(self.stages, self.shared)
        ]

    def _binary(
        self,
        stage_i: int,
        dl: Optional[UpdateBatch],
        dr: Optional[UpdateBatch],
        tick: int,
    ):
        stage = self.stages[stage_i]
        left_arr, right_arr = self.state[stage_i]
        lh, rh = self.shared[stage_i]
        outs = []
        dlk = arrange_batch(dl, stage.stream_key) if dl is not None else None
        drk = arrange_batch(dr, stage.lookup_key) if dr is not None else None
        # shared sides absorb the tick's delta first: thru(t) then includes
        # it, before(t) excludes it — the two views the update rule needs
        if lh is not None:
            lh.offer(tick, dlk)
        if rh is not None:
            rh.offer(tick, drk)
        if dlk is not None:
            right_batches = rh.thru(tick) if rh is not None else right_arr.batches
            outs += _probe(dlk, right_batches)
        if drk is not None:
            left_batches = lh.before(tick) if lh is not None else left_arr.batches
            outs += _probe(drk, left_batches, swap=True)
        if rh is None and dlk is not None and drk is not None:
            outs += _probe(dlk, [drk])  # arrange_batch consolidated drk
        if lh is None and dlk is not None:
            left_arr.insert(dlk, already_keyed=True)
        if rh is None and drk is not None:
            right_arr.insert(drk, already_keyed=True)
        return _union(outs)

    def step(self, tick, ins):
        errs = _union([d[1] for d in ins if d is not None])
        stream = ins[0][0] if ins[0] is not None else None
        for i in range(len(self.stages)):
            right = ins[i + 1][0] if ins[i + 1] is not None else None
            if self.shard is not None:
                st = self.stages[i]
                stream = self.shard.exchange(
                    self.channels[i][0], tick, stream, st.stream_key
                )
                right = self.shard.exchange(
                    self.channels[i][1], tick, right, st.lookup_key
                )
            stream = self._binary(i, stream, right, tick)
        if stream is None and errs is None:
            return None
        if stream is not None and self.closure is not None:
            stream, cerrs = self.closure.apply(stream)
            errs = _union([errs, cerrs])
        return stream, errs

    def compact(self, since):
        for l, r in self.state:
            if l is not None:
                l.compact(since)
            if r is not None:
                r.compact(since)

    def state_info(self):
        out = []
        for i, (l, r) in enumerate(self.state):
            lh, rh = self.shared[i]
            if l is not None:
                out += l.info_rows(f"join_stage{i}_left")
            else:
                out.append((f"join_stage{i}_left:{lh.name()}",) + _shared_state_info(lh))
            if r is not None:
                out += r.info_rows(f"join_stage{i}_right")
            else:
                out.append((f"join_stage{i}_right:{rh.name()}",) + _shared_state_info(rh))
        return out


class DeltaJoinNode(Node):
    """Delta join: one update path per input, streaming through the other
    inputs' arrangements with no intermediate state (delta_join.rs:51).

    Per tick, paths are processed in input order; input k's delta is inserted
    into k's arrangements after path k runs, so path k sees inputs j<k
    up-to-date and inputs j>k as of the previous paths — the sequential-update
    decomposition that half_join realizes with per-update time comparison.

    A stage's matches that fit a sixteenth of its probe come back as one
    batch of that floor, none included (`join_against`), and the path's
    later stages keep that floor; the paths' outputs whose matches fit the
    join's floor are compacted into one batch of it. So the few rows a
    selective stage lets through (a semijoin's: none in most ticks, a
    handful in some) run through the programs every tick before them ran,
    however many they are and whichever paths they came by.

    Each path that had input is a `join.path` span, and `stage_rows` holds the
    last step's rows per stage as the host already knew them, for
    `mzt_join_stage_rows_total`: `(path, stage, "out", n)` with n the count
    pass's total of rows matched by hash (what sizes the materialize pass),
    and `(path, stage, "in", n)` from a path's second stage on, the stage
    before's total. A path's own delta is not counted: that would take a read.
    """

    def __init__(
        self, jplan: lir.DeltaJoinPlan, closure, n_inputs: int, shard=None,
        shared=None,
    ):
        self.plan = jplan
        self.closure = closure
        self.shard = shard
        # (input, lookup_key) -> TraceHandle for inputs that are imported
        # collections: the per-input index reuse that delta joins exist for
        self.shared: dict = shared or {}
        self.stage_rows: list = []
        self.arrs: dict[tuple[int, tuple[int, ...]], Arrangement] = {}
        for path in jplan.paths:
            for st in path:
                key = (st.other_input, st.lookup_key)
                if key not in self.arrs and key not in self.shared:
                    self.arrs[key] = Arrangement(key_cols=st.lookup_key, merge_empty=True)
        if shard is not None:
            # one channel per half-join hop (the stream re-keys at every
            # stage) plus one per arrangement publish; allocation order is
            # plan order, identical on every worker
            self.path_channels = [
                [shard.alloc_channel() for _ in path] for path in jplan.paths
            ]
            self.arr_channels = {
                key: shard.alloc_channel()
                for key in list(self.arrs) + list(self.shared)
            }

    def _lookup_batches(self, k: int, st, tick: int) -> list:
        """Arrangement contents path k must see for stage `st`: shared
        traces expose the sequential-update decomposition by time (inputs
        j<k through t, j>k before t) instead of by insertion order."""
        key = (st.other_input, st.lookup_key)
        h = self.shared.get(key)
        if h is None:
            return self.arrs[key].batches
        return h.thru(tick) if st.other_input < k else h.before(tick)

    def _path(self, k: int, path, stream, tick: int):
        """Path k's stages over its stream (input k's delta); returns the
        stream as the last stage left it, or None, and the rows that stage
        matched by hash."""
        sharded = self.shard is not None
        floor = matched = 0
        for si, st in enumerate(path):
            if sharded:
                # every worker participates in every hop's exchange —
                # a worker with no local stream rows still punctuates
                stream = self.shard.exchange(
                    self.path_channels[k][si], tick, stream, st.stream_key
                )
            elif stream is None:
                break
            if stream is None:
                continue
            probe = arrange_batch(stream, st.stream_key)
            batches = self._lookup_batches(k, st, tick)
            totals = join_totals(probe, batches)
            if si and not sharded:
                self.stage_rows.append((k, si, "in", matched))
            matched = sum(totals)
            self.stage_rows.append((k, si, "out", matched))
            # a probe that is the last stage's batch at its floor keeps it
            floor = floor or bucket_cap(probe.cap // HEAD_RATIO)
            stream = _union(join_against(probe, batches, floor=floor, totals=totals))
            if matched > floor:
                floor = 0
        return stream, matched

    def step(self, tick, ins):
        errs = _union([d[1] for d in ins if d is not None])
        outs, matched = [], []
        sharded = self.shard is not None
        self.stage_rows = []
        # shared arrangements absorb their input's tick delta up front:
        # offers are idempotent (first reader wins) and the thru/before
        # views encode the per-path time split
        for (inp, key), h in self.shared.items():
            dk = ins[inp][0] if ins[inp] is not None else None
            routed = dk
            if sharded:
                routed = self.shard.exchange(
                    self.arr_channels[(inp, key)], tick, dk, key
                )
            h.offer(
                tick,
                arrange_batch(routed, key) if routed is not None else None,
            )
        for k, path in enumerate(self.plan.paths):
            dk = ins[k][0] if ins[k] is not None else None
            if dk is not None:
                with TRACER.span("join.path"):
                    stream, n = self._path(k, path, dk, tick)
            else:
                stream, n = self._path(k, path, dk, tick)
            if stream is not None:
                outs.append(_project(stream, self.plan.permutations[k]))
                matched.append(n)
            # now publish input k's delta to its PRIVATE arrangements
            # (sharded: the delta is exchanged by each arrangement's key
            # first, so every partitioned arrangement holds exactly the rows
            # it owns); shared ones were offered above
            for (inp, key), arr in self.arrs.items():
                if inp != k:
                    continue
                routed = dk
                if sharded:
                    routed = self.shard.exchange(
                        self.arr_channels[(inp, key)], tick, dk, key
                    )
                if routed is not None:
                    arr.insert(arrange_batch(routed, key), already_keyed=True)
        # the join's floor follows its inputs' delta capacities alone (none
        # where a worker's counts are its own, or in a bulk tick)
        widest = max((d[0].cap for d in ins if d is not None and d[0] is not None), default=0)
        floor = 0 if sharded or widest > BULK_ROWS else bucket_cap(widest // HEAD_RATIO)
        out = _union(gather_parts(lambda i, _cap: outs[i], matched, floor))
        if out is None and errs is None:
            return None
        if out is not None and self.closure is not None:
            out, cerrs = self.closure.apply(out)
            errs = _union([errs, cerrs])
        return out, errs

    def compact(self, since):
        for arr in self.arrs.values():
            arr.compact(since)

    def state_info(self):
        out = []
        for (inp, key), a in self.arrs.items():
            out += a.info_rows(f"delta_in{inp}_key{list(key)}")
        for (inp, key), h in self.shared.items():
            out.append(
                (f"delta_in{inp}_key{list(key)}:{h.name()}",)
                + _shared_state_info(h)
            )
        return out


# Past this many rows of capacity a batch is BULK: a hydration snapshot, a bulk
# load, and what operators make of one. Ordinary deltas are far narrower (at
# TPC-H SF1 Q3's and Q17's are 16,384 to 131,072 rows wide). Two rules hold for
# bulk batches only, so an ordinary tick's programs and host reads are what
# they were:
# - an accumulable reduce steps a bulk input slice by slice: the step's programs
#   hold several times their input in temporaries (the chip's compiler asks
#   9.2 GB for the 8,388,608-row snapshot of lineitem at SF1 under sum + count
#   by l_partkey, 2.3 GB for a slice; PERF.md section 6, PR 30), and the
#   self-correcting emission makes slices sound: a later slice retracts what an
#   earlier one emitted for the same group, and the union consolidates;
# - an operator's bulk output is sized by the rows it holds (`spine.sized`,
#   one host read) before the next operator sees it: outputs live until the
#   tick ends and every operator downstream costs by capacity, and a join's
#   filter or a reduce leaves a few rows, or no error at all, at the width of
#   the snapshot (Q17's hydration held 22 GB of such outputs at SF1).
# Not half of this: the chip's compiler builds the reduce step over 1,048,576
# rows as 753 MB of code in 429 s, over 2,097,152 rows as 171 MB in 110 s
# (v5e, asked without the chip; on the chip a bound of 2^20 took Q17's
# hydration from 560 s to over 1,150).
BULK_ROWS = 1 << 21


def _bulk_sized(batch: Optional[UpdateBatch]) -> Optional[UpdateBatch]:
    return sized(batch) if batch is not None and batch.cap > BULK_ROWS else batch


@partial(jax.jit, static_argnums=(1, 2))
def _rows(batch: UpdateBatch, lo: int, hi: int) -> UpdateBatch:
    return jax.tree_util.tree_map(lambda x: x[lo:hi], batch)


def _reduce_in_slices(reducer, tick: int, oks: UpdateBatch):
    """Steps `reducer` (its accumulator table `state`, its `_step_one(tick,
    delta) -> (out, errs, changed)`) over `oks`: as one step where it is no
    wider than BULK_ROWS (every ordinary tick), else over its slices. Every
    slice meets the table at BULK_ROWS of capacity or more (the step cuts it
    back to its groups): one program for all slices, where a table growing
    from slice to slice would ask for one each, at two minutes of the chip's
    compiler apiece. A slice's output is sized by the rows it can hold (a
    changed group emits at most a retraction and an insertion; the kernels
    leave an output at twice its input's capacity). Returns the same triple;
    `reducer.collided` says whether some step's table merge flagged a
    double collision."""
    reducer.collided = False
    if oks.cap <= BULK_ROWS:
        return reducer._step_one(tick, oks)
    outs, errs, changed = [], [], 0
    for lo in range(0, oks.cap, BULK_ROWS):
        reducer.state = reducer.state.with_capacity(max(reducer.state.cap, BULK_ROWS))
        out, e, n = reducer._step_one(tick, _rows(oks, lo, min(lo + BULK_ROWS, oks.cap)))
        outs.append(sized(out, 2 * n, slack=1))
        errs.append(_bulk_sized(e))
        changed += n  # a group counts once per slice that changed it
    return _union(outs), _union(errs), changed


class ReduceNode(Node):
    def __init__(self, expr: lir.Reduce, in_dtypes: tuple):
        self.key_cols = expr.key_cols
        self.aggs = expr.aggs
        key_dtypes = tuple(in_dtypes[i] for i in expr.key_cols)
        accum_dtypes = tuple(np.dtype(a.accum_dtype) for a in expr.aggs)
        self.state = AccumState.empty(8, key_dtypes, accum_dtypes)
        self.groups = 0  # live groups, as the last step read them
        self.changed = None  # groups whose output changed in the last step (None: no step)
        self.errs_carried = None  # the last step handed on an error batch
        self.collided = False  # the last step's table merge flagged a double collision

    def step(self, tick, ins):
        self.changed = None
        d = ins[0]
        if d is None:
            return None
        oks, errs = d
        if oks is None:
            return None if errs is None else (None, errs)
        out, agg_errs, self.changed = _reduce_in_slices(self, tick, oks)
        self.errs_carried = agg_errs is not None
        return out, _union([errs, agg_errs])

    def _step_one(self, tick, delta):
        self.state, out, agg_errs, counts = accumulable_step(
            self.state, delta, self.key_cols, self.aggs, tick
        )
        self.groups, changed, agg_errs, dup = read_step_counts(counts, agg_errs)
        self.collided |= dup
        # the step leaves the table at cap(state) + cap(delta): back to the
        # pow2 bucket of its groups (AccumState.rebucketed, on the count read above)
        self.state = self.state.with_capacity(bucket_cap(self.groups))
        return out, agg_errs, changed

    def state_info(self):
        return [("reduce_accums", 1, self.state.cap, self.groups)]


class SharedReduceNode(Node):
    """Accumulable reduce over a shared aggregate trace: the accumulator
    table steps ONCE per tick across every reader (SharedReduceTrace
    memoizes the emission), and an importing dataflow hydrates from the
    trace's cumulative output snapshot instead of re-aggregating its input
    snapshot."""

    def __init__(self, handle):
        self.h = handle
        self.changed = None
        self.errs_carried = None
        self.collided = False

    @property
    def groups(self) -> int:
        return self.h.trace.groups

    def step(self, tick, ins):
        self.changed = None
        d = ins[0]
        if self.h._hydrating(tick):
            if self.h.trusted:
                # live peek: the shared state already reflects the collection
                # through this tick; the input snapshot is the telescoped
                # history it was built from and must not be double-applied
                out, agg_errs = self.h.trace.snapshot(tick)
            else:
                # installed import: the trace is NOT trusted at as_of (a
                # reconciliation replay re-creates dataflows before any
                # re-stepping) — aggregate our own input snapshot privately;
                # the shared state takes over from the first post-as_of tick
                out, agg_errs = self._private_hydration(tick, d)
            errs = _union([d[1] if d is not None else None, agg_errs])
            if out is None and errs is None:
                return None
            return out, errs
        if d is None:
            return None
        oks, errs = d
        if oks is None:
            return None if errs is None else (None, errs)
        stepped = tick > self.h.trace.frontier  # else another reader's step is replayed
        out, agg_errs = self.h.trace.step(tick, oks, _reduce_in_slices)
        if stepped:
            self.changed = self.h.trace.changed
            self.errs_carried = agg_errs is not None
            self.collided = self.h.trace.collided
        return out, _union([errs, agg_errs])

    def _private_hydration(self, tick, d):
        """Aggregate the hydration snapshot against an empty throwaway
        accumulator (exactly what a private ReduceNode would emit)."""
        if d is None or d[0] is None:
            return None, None
        tr = self.h.trace
        scratch = AccumState.empty(
            8,
            tuple(k.dtype for k in tr.state.keys),
            tuple(a.dtype for a in tr.state.accums),
        )
        _state, out, errs, _counts = accumulable_step(
            scratch, d[0], tr.key_cols, tr.aggs, tick
        )
        return out, errs

    def state_info(self):
        return [(self.h.name(),) + _shared_state_info(self.h)]


# The least capacity of a reduce's or a DISTINCT's table: one whose groups
# are few and come and go (a view's answer behind a HAVING or an IN) would
# give its step a new shape in whichever tick first took it across 8, 16,
# ..., as the program's first tick at that size. A large table's count moves
# by a small share of it from tick to tick.
MIN_TABLE = HEAD_RATIO * MIN_CAP


class FusedMfpReduceNode(Node):
    """Mfp→Reduce rendered as one compiled tick (ops/fused_reduce.py).

    State capacity is sticky (grow-only pow2, at least MIN_TABLE) so shapes
    recur and the jit cache stays warm across ticks.
    """

    def __init__(self, mfp, expr: lir.Reduce, mfp_out_dtypes: tuple):
        from ..ops.reduce import AccumState as _AS

        self.mfp = mfp
        self.key_cols = expr.key_cols
        self.aggs = expr.aggs
        key_dtypes = tuple(mfp_out_dtypes[i] for i in expr.key_cols)
        accum_dtypes = tuple(np.dtype(a.accum_dtype) for a in expr.aggs)
        self.state = _AS.empty(8, key_dtypes, accum_dtypes)
        self.state_cap = MIN_TABLE
        self.groups = 0
        self.changed = None
        self.errs_carried = None
        self.collided = False

    def step(self, tick, ins):
        self.changed = None
        d = ins[0]
        if d is None:
            return None
        oks, errs = d
        if oks is None:
            return None if errs is None else (None, errs)
        out, agg_errs, self.changed = _reduce_in_slices(self, tick, oks)
        self.errs_carried = agg_errs is not None
        return out, _union([errs, agg_errs])

    def _step_one(self, tick, delta):
        from ..ops.fused_reduce import fused_mfp_reduce_step

        self.state, out, agg_errs, counts = fused_mfp_reduce_step(
            self.state, delta, tick, self.mfp, self.key_cols, self.aggs
        )
        self.groups, changed, agg_errs, dup = read_step_counts(counts, agg_errs)
        self.collided |= dup
        if bucket_cap(self.groups) > self.state_cap:
            self.state_cap = bucket_cap(self.groups)
        self.state = self.state.with_capacity(self.state_cap)
        return out, agg_errs, changed

    def state_info(self):
        return [("fused_reduce_accums", 1, self.state.cap, self.groups)]


_REDUCE_NODES = (ReduceNode, SharedReduceNode, FusedMfpReduceNode)
_REDUCE_LABELS = ("dataflow", "operator")
_REDUCE_STEP_NS = obs_metrics.REGISTRY.histogram(
    "mzt_reduce_step_duration_ns",
    "host wall of one accumulable reduce operator's step that had input",
    labels=_REDUCE_LABELS,
)
_REDUCE_CHANGED = obs_metrics.REGISTRY.counter(
    "mzt_reduce_groups_changed_total",
    "groups whose output row changed (appeared, vanished or took a new value) in a reduce step",
    labels=_REDUCE_LABELS,
)
_REDUCE_GROUPS = obs_metrics.REGISTRY.gauge(
    "mzt_reduce_state_groups",
    "live groups in a reduce operator's accumulator table after its last step",
    labels=_REDUCE_LABELS,
)
_REDUCE_ERR_BATCHES = obs_metrics.REGISTRY.counter(
    "mzt_reduce_error_batches_total",
    "reduce steps by their own error batch: held rows and was handed on (carried), or held none and was not (empty)",
    labels=_REDUCE_LABELS + ("outcome",),
)
_REDUCE_STATE_MERGES = obs_metrics.REGISTRY.counter(
    "mzt_reduce_state_merges_total",
    "reduce steps by their table update: the delta merged into the table (merged), or the merge flagged a packed-key double collision, an error row (collision)",
    labels=_REDUCE_LABELS + ("outcome",),
)

_TOPK_STEP_NS = obs_metrics.REGISTRY.histogram(
    "mzt_topk_step_duration_ns",
    "host wall of one TopK operator's step that had input",
    labels=_REDUCE_LABELS,
)
_TOPK_REGATHERED = obs_metrics.REGISTRY.counter(
    "mzt_topk_rows_regathered_total",
    "rows of the affected groups a TopK step gathered back from its state (both gathers of a general TopK)",
    labels=_REDUCE_LABELS,
)
_JOIN_STAGE_ROWS = obs_metrics.REGISTRY.counter(
    "mzt_join_stage_rows_total",
    "rows into and out of a delta join's stage, as the host already knew them: out is the count pass's matches by hash",
    labels=_REDUCE_LABELS + ("path", "stage", "side"),
)

_ABSENT = object()


class BasicAggNode(Node):
    """ReducePlan::Basic — string_agg / array_agg / list_agg.

    Maintains per-group element multisets host-side (strings are host data;
    the device only carries dictionary codes) and re-renders affected groups
    each tick as a retract/insert pair — the same emission discipline as the
    accumulable reduce's (-old, +new) self-correction. Element order in the
    rendered value is the decoded elements' sort order (deterministic under
    churn; the reference leaves no-ORDER-BY order unspecified).
    Reference: AggregateFunc's Basic class, render/reduce.rs:196.

    Known cost: each re-render interns a new string into the engine's
    append-only dictionary (repr/types.py StringDictionary has no eviction),
    so a group that churns every tick grows dictionary memory by one
    rendering per change; cycles back to a previous rendering reuse its
    code. Tracked via state_info's rendered-bytes column so the memory
    limiter and introspection can see it.
    """

    def __init__(self, e, in_dtypes: tuple):
        from ..expr.scalar import null_sentinel

        self.nk = len(e.key_cols)
        self.func = e.func
        self.delim, self.argtype, self.dct = e.extra
        self.in_dtypes = tuple(np.dtype(d) for d in in_dtypes)
        el_dt = self.in_dtypes[self.nk]
        self.el_null = (
            None if el_dt.kind == "f" else int(null_sentinel(el_dt))
        )
        self.groups: dict = {}  # key tuple -> {element raw value: count}
        self.current: dict = {}  # key tuple -> emitted rendered code (or None)

    def _decode_el(self, el):
        from ..expr.strings import decode_storage_value

        return decode_storage_value(self.argtype, el, self.dct, bool_style="tf")

    def _render(self, multiset: dict):
        """Rendered value (python str) or None (SQL NULL) for one group."""
        distinct, nulls = [], 0
        for el, cnt in multiset.items():
            if cnt < 0:
                raise ValueError("basic aggregate saw net-negative multiplicity")
            if el is None or el == self.el_null:
                nulls += cnt
            else:
                rendered = self._decode_el(el)
                # order by VALUE (strings/jsonb by canonical text, numbers
                # numeric), never by dictionary code — codes are insertion-
                # ordered and vary across interning histories
                sk = rendered if self.argtype in ("str", "jsonb") else el
                distinct.append((sk, rendered, cnt))
        if self.func in ("min_str", "max_str"):
            # min/max over decoded strings (device top-1 would rank by
            # dictionary code — insertion order, not collation); O(distinct),
            # no multiplicity expansion
            if not distinct:
                return None
            pick = min if self.func == "min_str" else max
            return pick(distinct, key=lambda p: p[0])[1]
        live = []
        for sk, rendered, cnt in sorted(distinct, key=lambda p: p[0]):
            live.extend([rendered] * cnt)
        if self.func == "string_agg":
            # string_agg skips NULL inputs; an all-NULL group is NULL
            return self.delim.join(live) if live else None
        if self.func == "jsonb_agg":
            import json as _json

            at = self.argtype

            def as_json(r):
                if at == "jsonb":
                    return _json.loads(r)
                if at == "int" or (isinstance(at, tuple) and at[0] == "numeric"):
                    return float(r) if "." in r else int(r)
                if at == "float":
                    return float(r)
                if at == "bool":
                    return r == "t"
                return r  # strings stay JSON strings

            elements = [as_json(r) for r in live] + [None] * nulls
            return _json.dumps(elements, separators=(",", ":"))
        # array_agg / list_agg keep NULL elements (pg semantics), NULLs last

        def q(s: str) -> str:
            if (
                s == ""
                or any(ch in '{},"\\' or ch.isspace() for ch in s)
                or s.upper() == "NULL"
            ):
                return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'
            return s

        parts = [q(s) for s in live] + ["NULL"] * nulls
        return "{" + ",".join(parts) + "}"

    def step(self, tick, ins):
        d = ins[0]
        if d is None:
            return None
        oks, errs = d
        if oks is None:
            return None if errs is None else (None, errs)
        affected = set()
        for vals, _t, diff in oks.to_rows():
            k = tuple(vals[: self.nk])
            el = vals[self.nk]
            g = self.groups.setdefault(k, {})
            g[el] = g.get(el, 0) + diff
            if g[el] == 0:
                del g[el]
            if not g:
                del self.groups[k]
            affected.add(k)
        out = []  # (key tuple, code-or-None, diff)
        for k in affected:
            old = self.current.get(k, _ABSENT)
            if k in self.groups:
                r = self._render(self.groups[k])
                new = None if r is None else self.dct.encode(r)
            else:
                new = _ABSENT
            if old is new or (old is not _ABSENT and new is not _ABSENT and old == new):
                continue
            if old is not _ABSENT:
                out.append((k, old, -1))
            if new is not _ABSENT:
                out.append((k, new, 1))
                self.current[k] = new
            else:
                self.current.pop(k, None)
        if not out:
            return None, errs
        from ..expr.scalar import NULL_I64, null_sentinel

        cols = []
        for i in range(self.nk):
            dt = self.in_dtypes[i]
            fill = np.nan if dt.kind == "f" else 0
            cols.append(
                np.array(
                    [fill if row[0][i] is None else row[0][i] for row in out],
                    dtype=dt,
                )
            )
        cols.append(
            np.array(
                [NULL_I64 if c is None else c for _k, c, _d in out], dtype=np.int64
            )
        )
        times = np.full(len(out), int(tick), dtype=np.uint64)
        diffs = np.array([d_ for _k, _c, d_ in out], dtype=np.int64)
        batch = UpdateBatch.build((), tuple(cols), times, diffs)
        return batch, errs

    def state_info(self):
        n = sum(len(g) for g in self.groups.values())
        rendered_bytes = sum(
            0 if c is None else len(self.dct.decode(c)) for c in self.current.values()
        )
        return [
            ("basic_agg_groups", 1, max(n, 1), len(self.groups)),
            ("basic_agg_rendered_bytes", 1, max(rendered_bytes, 1), rendered_bytes),
        ]


class DistinctNode(Node):
    """ReducePlan::Distinct — project to key cols, then presence per row.

    The table's capacity only grows, from MIN_TABLE (as
    `FusedMfpReduceNode.state_cap`): a count that moves back and forth
    across a power of two would give every step program a new shape each
    time it crossed."""

    def __init__(self, key_cols: tuple[int, ...], in_dtypes: tuple):
        self.key_cols = key_cols
        key_dtypes = tuple(in_dtypes[i] for i in key_cols)
        self.state = AccumState.empty(8, key_dtypes, ())
        self.state_cap = MIN_TABLE

    def step(self, tick, ins):
        d = ins[0]
        if d is None:
            return None
        oks, errs = d
        if oks is None:
            return None if errs is None else (None, errs)
        projected = _project(oks, self.key_cols)
        self.state, out, coll = threshold_step(
            self.state, projected, "distinct", tick
        )
        self.state_cap = max(self.state_cap, bucket_cap(int(self.state.count())))
        self.state = self.state.with_capacity(self.state_cap)
        return out, _union([errs, coll])

    def state_info(self):
        return [("distinct_accums", 1, self.state.cap, int(self.state.count()))]


class ThresholdNode(Node):
    def __init__(self, in_dtypes: tuple):
        self.state = AccumState.empty(8, tuple(in_dtypes), ())

    def step(self, tick, ins):
        d = ins[0]
        if d is None:
            return None
        oks, errs = d
        if oks is None:
            return None if errs is None else (None, errs)
        self.state, out, coll = threshold_step(self.state, oks, "threshold", tick)
        self.state = self.state.rebucketed()
        return out, _union([errs, coll])

    def state_info(self):
        return [("threshold_accums", 1, self.state.cap, int(self.state.count()))]


class TopKNode(Node):
    """TopK over a general input (ops/topk.py): the affected groups'
    contents are gathered from the input arrangement before and after the
    delta. `regathered` is the rows both gathers matched in the last step
    that had input (None: no such step), from the totals the host reads to
    size them."""

    def __init__(self, tplan):
        self.plan = tplan
        self.arr = Arrangement(key_cols=tplan.group_cols, merge_empty=True)
        self.regathered = None

    def step(self, tick, ins):
        self.regathered = None
        d = ins[0]
        if d is None:
            return None
        oks, errs = d
        if oks is None:
            return None if errs is None else (None, errs)
        keyed = arrange_batch(oks, self.plan.group_cols)
        totals: list = []
        out = topk_step(self.arr, keyed, self.plan, tick, totals)
        self.regathered = sum(totals)
        return out, errs

    def compact(self, since):
        self.arr.compact(since)

    def state_info(self):
        return self.arr.info_rows("topk_input")


class WindowNode(Node):
    """Window functions via affected-partition recompute (ops/window.py)."""

    def __init__(self, wplan):
        self.plan = wplan
        self.arr = Arrangement(key_cols=wplan.partition_cols)

    def step(self, tick, ins):
        from ..ops.window import window_step

        d = ins[0]
        if d is None:
            return None
        oks, errs = d
        if oks is None:
            return None if errs is None else (None, errs)
        keyed = arrange_batch(oks, self.plan.partition_cols)
        out = window_step(self.arr, keyed, self.plan, tick)
        return out, errs

    def compact(self, since):
        self.arr.compact(since)

    def state_info(self):
        return self.arr.info_rows("window_input")


class MonotonicTopKNode(Node):
    """TopK over an append-only input: state is only the current winners.

    The reference's MonotonicTop1/MonotonicTopK plans (plan/top_k.rs:28,
    render/top_k.rs:772 thinning): with no retractions possible, the new
    top-k of a group is always a subset of {stored winners} ∪ {new rows}, so
    the node stores the top (offset+limit) rows per touched group instead of
    the whole input — the input arrangement disappears entirely.
    `regathered` is TopKNode's: the stored winners' rows the gather matched.
    """

    def __init__(self, tplan):
        assert tplan.limit is not None
        self.plan = tplan
        self.keep = tplan.offset + tplan.limit
        self.out_arr = Arrangement(key_cols=tplan.group_cols)
        self.regathered = None

    def step(self, tick, ins):
        from ..ops.topk import distinct_keys, gather_groups, negate, topk_select

        self.regathered = None
        d = ins[0]
        if d is None:
            return None
        oks, errs = d
        if oks is None:
            return None if errs is None else (None, errs)
        if int(jnp.sum(jnp.where(oks.live, (oks.diffs < 0).astype(jnp.int32), 0))) > 0:
            raise RuntimeError(
                "monotonic top-k saw a retraction; plan must use the general path"
            )
        keyed = arrange_batch(oks, self.plan.group_cols)
        probes = distinct_keys(keyed)
        vdt = tuple(v.dtype for v in keyed.vals)
        totals: list = []
        old_kept = gather_groups(probes, self.out_arr.batches, tick, vdt, totals)
        self.regathered = sum(totals)
        cand = consolidate(UpdateBatch.concat(old_kept, keyed))
        nl = self.plan.nulls_last
        new_kept = topk_select(cand, self.plan.order_by, self.keep, 0, tick, nl)
        new_window = topk_select(
            cand, self.plan.order_by, self.plan.limit, self.plan.offset, tick, nl
        )
        old_window = topk_select(
            old_kept, self.plan.order_by, self.plan.limit, self.plan.offset, tick, nl
        )
        out = consolidate(UpdateBatch.concat(new_window, negate(old_window)))
        state_delta = consolidate(
            UpdateBatch.concat(new_kept, negate(_retime(old_kept, tick)))
        )
        self.out_arr.insert(state_delta)
        return out, errs

    def compact(self, since):
        self.out_arr.compact(since)

    def state_info(self):
        return self.out_arr.info_rows("monotonic_topk_winners")


_TOPK_NODES = (TopKNode, MonotonicTopKNode)


def _topk_output(ops: list, ref) -> bool:
    """Whether the rendered operator at `ref` hands on a TopK's rows,
    through Mfps."""
    while isinstance(ref, int):
        node, in_refs = ops[ref]
        if isinstance(node, _TOPK_NODES):
            return True
        if not isinstance(node, MfpNode):
            return False
        ref = in_refs[0]
    return False


class TemporalFilterNode(Node):
    """Validity windows: emit +row when its window opens, −row when it closes.

    Future events wait in a pending batch whose times are the scheduled event
    times; every tick flushes events ≤ tick (the temporal-bucketing shape,
    reference extensions/temporal_bucket.rs). Runs every tick even without
    input — the passage of time alone retracts expired rows.
    """

    def __init__(self, expr):
        self.lowers = tuple(expr.lowers)
        self.uppers = tuple(expr.uppers)
        self.pending: Optional[UpdateBatch] = None

    def _windows(self, batch: UpdateBatch):
        from ..expr.scalar import eval_expr
        from ..repr.batch import MAX_DEVICE_TIME, PAD_TIME, TIME_DTYPE

        cols = list(batch.vals)
        n = batch.cap
        # event times come from DATA values: clamp into [0, MAX_DEVICE_TIME]
        # so a huge bound saturates at "effectively forever" and can never
        # collide with the PAD_TIME padding sentinel (end == PAD_TIME means
        # "no expiry" below and must stay unreachable for real bounds)
        start = jnp.zeros((n,), dtype=TIME_DTYPE)
        for e in self.lowers:
            v, _err = eval_expr(e, cols, n)
            v = jnp.clip(v, 0, MAX_DEVICE_TIME).astype(TIME_DTYPE)
            start = jnp.maximum(start, v)
        end = jnp.full((n,), PAD_TIME, dtype=TIME_DTYPE)
        for e in self.uppers:
            v, _err = eval_expr(e, cols, n)
            v = jnp.clip(v, 0, MAX_DEVICE_TIME).astype(TIME_DTYPE)
            end = jnp.minimum(end, v)
        # a row's events: +d at max(start, row time), −d at end (if finite)
        start = jnp.maximum(start, batch.times)
        return start, end

    def step(self, tick, ins):
        from ..repr.batch import PAD_TIME
        from ..repr.hashing import PAD_HASH

        errs = None
        d = ins[0] if ins else None
        if d is not None:
            oks, errs = d
            if oks is not None:
                start, end = self._windows(oks)
                live = oks.live & (start < end)
                plus = UpdateBatch(
                    jnp.where(live, oks.hashes, PAD_HASH),
                    oks.keys,
                    oks.vals,
                    jnp.where(live, start, PAD_TIME),
                    jnp.where(live, oks.diffs, 0),
                )
                has_end = live & (end != PAD_TIME)
                minus = UpdateBatch(
                    jnp.where(has_end, oks.hashes, PAD_HASH),
                    oks.keys,
                    oks.vals,
                    jnp.where(has_end, end, PAD_TIME),
                    jnp.where(has_end, -oks.diffs, 0),
                )
                events = UpdateBatch.concat(plus, minus)
                self.pending = (
                    events
                    if self.pending is None
                    else UpdateBatch.concat(self.pending, events)
                )
        if self.pending is None:
            return None if errs is None else (None, errs)
        # flush events due at or before this tick
        from ..repr.batch import device_time_scalar

        due = self.pending.live & (self.pending.times <= device_time_scalar(tick))
        n_due = int(jnp.sum(due))
        if n_due == 0:
            out = None
        else:
            p = self.pending
            out = consolidate(
                UpdateBatch(
                    jnp.where(due, p.hashes, PAD_HASH),
                    p.keys,
                    p.vals,
                    p.times,
                    jnp.where(due, p.diffs, 0),
                )
            )
            remaining = consolidate(
                UpdateBatch(
                    jnp.where(due, PAD_HASH, p.hashes),
                    p.keys,
                    p.vals,
                    jnp.where(due, PAD_TIME, p.times),
                    jnp.where(due, 0, p.diffs),
                )
            )
            n_rem = int(remaining.count())
            self.pending = (
                None if n_rem == 0 else remaining.with_capacity(bucket_cap(n_rem))
            )
        if out is None and errs is None:
            return None
        return out, errs

    def state_info(self):
        n = 0 if self.pending is None else int(self.pending.count())
        cap = 0 if self.pending is None else self.pending.cap
        return [("temporal_pending", 1, cap, n)]


class LetRecNode(Node):
    """Iterate bindings to fixpoint within each outer tick.

    An inner incremental Dataflow hosts the bindings and body; its private
    timestamp is the iteration counter, so each iteration's work is
    proportional to the CHANGE since the previous iterate — exactly
    differential's iterate/Variable semantics on the inner coordinate of a
    product timestamp (reference: render.rs:365,887). The outer output delta
    is the telescoped sum of per-iteration body deltas, retimed to the tick.
    """

    def __init__(self, expr):
        self.expr = expr
        self.rec_ids = [b[0] for b in expr.bindings]
        self.external_ids = list(expr.external_ids)
        self.max_iters = expr.max_iters
        src = {gid: dts for gid, dts in expr.ext_dtypes}
        for gid, _plan, dts in expr.bindings:
            src[gid] = dts
        builds = [lir.BuildDesc(gid, plan, dts) for gid, plan, dts in expr.bindings]
        builds.append(lir.BuildDesc("__letrec_body__", expr.body, expr.body_dtypes))
        desc = lir.DataflowDescription(
            source_imports=src,
            objects_to_build=builds,
            index_exports={},
        )
        self.inner = Dataflow(desc)
        self.inner_time = 0
        self.started = False

    def step(self, tick, ins):
        ext: dict = {}
        errs_parts = []
        for eid, d in zip(self.external_ids, ins):
            if d is None:
                continue
            if d[0] is not None:
                ext[eid] = d[0]
            if d[1] is not None:
                errs_parts.append(d[1])
        if not ext and self.started:
            return None if not errs_parts else (None, _union(errs_parts))
        self.started = True

        out = None
        deltas = dict(ext)
        for _it in range(self.max_iters):
            self.inner_time += 1
            results = self.inner.step(self.inner_time, deltas)
            deltas = {}
            converged = True
            for rec_id in self.rec_ids:
                d = results.get(rec_id)
                if d is None:
                    continue
                if d[1] is not None and int(d[1].count()) > 0:
                    errs_parts.append(_retime(d[1], tick))
                if d[0] is not None and int(d[0].count()) > 0:
                    deltas[rec_id] = d[0]
                    converged = False
            body = results.get("__letrec_body__")
            if body is not None:
                if body[0] is not None:
                    # folded in as it comes and held at the pow2 bucket of
                    # its rows: one union at the end would have the summed
                    # capacity, a shape that follows the iteration count
                    out = _union([out, _retime(body[0], tick)])
                    out = out.with_capacity(bucket_cap(int(out.count())))
                if body[1] is not None and int(body[1].count()) > 0:
                    errs_parts.append(_retime(body[1], tick))
            if converged:
                break
        else:
            raise RuntimeError(
                f"WITH MUTUALLY RECURSIVE did not converge in {self.max_iters} iterations"
            )
        errs = _union(errs_parts) if errs_parts else None
        if out is None and errs is None:
            return None
        return out, errs

    def state_info(self):
        return [
            (f"letrec:{name}", nb, cap, rec)
            for _obj, _op, name, nb, cap, rec, _b in self.inner.arrangement_info()
        ]


def peek_row_key(row: tuple) -> tuple:
    """THE canonical peek output order (NULLs last per column). Every reader
    that merges or re-sorts peek rows — materialize_counts here, the sharded
    controller's cross-shard merge — must share this key, or sharded results
    drift from the 1-process byte-identical contract."""
    return tuple((v is None, 0 if v is None else v) for v in row)


def row_bytes_estimate(data: tuple) -> int:
    """Rough wire size of one result row — the accounting unit for
    max_result_size budgets: tuple overhead + 8 B/column, plus the actual
    payload of string/bytes values (decoded rows carry real strings; a flat
    per-column charge would let a wide-TEXT result blow past the budget
    unnoticed). Encoded rows hold dictionary codes (ints), where the flat
    charge is exact."""
    n = 16 + 8 * len(data)
    for v in data:
        if isinstance(v, (str, bytes)):
            n += len(v)
    return n


def materialize_counts(
    acc: dict, label: str, byte_budget: int | None = None
) -> list[tuple]:
    """Expand {row: multiplicity} into sorted rows; negative multiplicities
    mean upstream inconsistency and error (the reference surfaces these as
    'Invalid data in source, saw retractions' rather than masking).

    `byte_budget` bounds the EXPANSION itself: a small consolidated trace can
    carry huge multiplicities, so the max_result_size check must abort here —
    mid-expansion, before the full result ever exists in memory — with the
    canonical 53400, not after the list is built."""
    from ..errors import ResultSizeExceeded

    rows: list[tuple] = []
    spent = 0
    key = lambda kv: peek_row_key(kv[0])
    for data, cnt in sorted(acc.items(), key=key):
        if cnt < 0:
            raise RuntimeError(
                f"peek {label}: negative multiplicity {cnt} for {data}"
            )
        if byte_budget is not None and cnt:
            spent += row_bytes_estimate(data) * cnt
            if spent > byte_budget:
                raise ResultSizeExceeded(
                    f"result exceeds max_result_size ({byte_budget} bytes); "
                    f"aborted after ~{len(rows)} rows"
                )
        rows.extend([data] * cnt)
    return rows


def peek_error_message(index_id: str, acc: dict) -> str:
    """Human-readable message for a non-empty error collection: decodes
    EvalErr codes from error rows (which carry (code, ...) tuples) — shared
    by the host-path and fused-path peeks so both render identically."""
    from ..expr.scalar import EvalErr

    def _msg(data):
        try:
            return EvalErr(int(data[0])).name.lower().replace("_", " ")
        except (ValueError, TypeError, IndexError):
            return str(data)

    msgs = sorted({_msg(d) for d, v in acc.items() if v > 0})
    return f"peek {index_id}: error: {'; '.join(msgs)}"


def _retime(batch: UpdateBatch, tick: int) -> UpdateBatch:
    """Overwrite live rows' times with the outer tick (iteration timestamps
    are scope-private, like the inner coordinate of a product timestamp)."""
    from ..repr.batch import to_device_time

    t = to_device_time(tick)
    live = batch.live
    return UpdateBatch(
        batch.hashes,
        batch.keys,
        batch.vals,
        jnp.where(live, t, batch.times),
        batch.diffs,
    )


# ---------------------------------------------------------------------------
# dataflow
# ---------------------------------------------------------------------------


def _node_state_bytes(node, rows: list) -> list:
    """Per-state_info-row byte counts for one node, aligned with `rows`
    (its state_info() output). Dispatch mirrors bench_shared_mvs.py's
    _state_objects: owners charge their arrangements/accumulators, shared
    importers charge zero."""
    if isinstance(node, ArrangeByNode):
        return _arr_row_nbytes(node.arr)
    if isinstance(node, (SharedArrangeNode, SharedReduceNode)):
        return [_shared_handle_nbytes(node.h)]
    if isinstance(node, LinearJoinNode):
        out = []
        for (l, r), (lh, rh) in zip(node.state, node.shared):
            out += _arr_row_nbytes(l) if l is not None else [_shared_handle_nbytes(lh)]
            out += _arr_row_nbytes(r) if r is not None else [_shared_handle_nbytes(rh)]
        return out
    if isinstance(node, DeltaJoinNode):
        return [n for a in node.arrs.values() for n in _arr_row_nbytes(a)] + [
            _shared_handle_nbytes(h) for h in node.shared.values()
        ]
    if isinstance(node, (ReduceNode, FusedMfpReduceNode, DistinctNode, ThresholdNode)):
        return [accum_state_nbytes(node.state)]
    if isinstance(node, BasicAggNode):
        # (groups, rendered_bytes) rows: host dicts are uncharged, the
        # rendered-bytes row's record count IS its byte figure
        return [0] + [r[3] for r in rows[1:]]
    if isinstance(node, (WindowNode, TopKNode)):
        return _arr_row_nbytes(node.arr)
    if isinstance(node, MonotonicTopKNode):
        return _arr_row_nbytes(node.out_arr)
    if isinstance(node, TemporalFilterNode):
        return [0 if node.pending is None else batch_nbytes(node.pending)]
    if isinstance(node, LetRecNode):
        return [b for *_rest, b in node.inner.arrangement_info()]
    return [0] * len(rows)


@dataclass
class _Rendered:
    node: Node
    input_ids: list  # each is an id (str) or nested _Rendered


class Dataflow:
    """A rendered dataflow: drive with `step`, read indexes with `peek`.

    The tick loop is the host analogue of the timely worker loop
    (src/compute/src/server.rs:356): advance the input frontier, flow deltas
    through the operator DAG in dependency order, update exported traces.
    """

    def __init__(
        self,
        desc: lir.DataflowDescription,
        shard: ShardContext | None = None,
        traces=None,
        trace_reader: str | None = None,
        trace_export: bool = True,
        operator_logging: bool = False,
    ):
        # `shard`: render as ONE worker of a multi-process sharded replica —
        # exchange pacts are inserted in front of every stateful operator and
        # all workers must step the same tick sequence (see cluster/mesh.py)
        #
        # `traces`: a TraceManager for cross-dataflow arrangement sharing
        # (arrangement/trace_manager.py). Stateful operators over imported
        # collections import a matching shared trace when one exists, else
        # build and EXPORT one for later dataflows; every use registers
        # `trace_reader`'s since hold at desc.as_of. `trace_export=False`
        # (ephemeral peek dataflows) imports only — a trace exported by a
        # dataflow that dies after one tick would go stale immediately.
        self.shard = shard
        self.traces = traces
        self._trace_reader = trace_reader
        self._trace_export = trace_export
        self._trace_handles: dict = {}
        self.desc = desc
        self.has_temporal = False  # temporal filters need stepping every tick
        self.builds: list = []  # (obj_id, [(node, input_refs)], out_ref)
        self.dtypes: dict[str, tuple] = {}
        for sid, dts in desc.source_imports.items():
            self.dtypes[sid] = tuple(dts)
        for bd in desc.objects_to_build:
            ops = []
            self._memo: dict[int, object] = {}
            out_ref = self._render(bd.plan, ops)
            self.builds.append((bd.id, ops, out_ref))
            self.dtypes[bd.id] = tuple(bd.dtypes)
        # objects whose rows are a TopK's (through Mfps): they move in few
        # ticks, and the arrangements that hold them merge empty deltas too
        self.topk_outputs = {obj_id for obj_id, ops, out_ref in self.builds if _topk_output(ops, out_ref)}
        self.index_traces: dict[str, Arrangement] = {}
        self.index_errs: dict[str, Arrangement] = {}
        for idx_id, (obj_id, key_cols) in desc.index_exports.items():
            self.index_traces[idx_id] = Arrangement(
                key_cols=tuple(key_cols), merge_empty=obj_id in self.topk_outputs
            )
            self.index_errs[idx_id] = Arrangement(key_cols=())
        self.sink_outputs: dict[str, list] = {s: [] for s in desc.sink_exports}
        from .antichain import EMPTY, Antichain

        self._frontier = Antichain.of(desc.as_of)
        self._last_complete = desc.as_of - 1
        # `until`: outputs at times ≥ until are not needed; empty = unbounded
        # (reference dataflows.rs:54-74 — one-shot peek dataflows set
        # until = as_of+1 so temporal filters need not emit the future)
        self.until = (
            Antichain.of(desc.until) if getattr(desc, "until", None) is not None
            else EMPTY
        )
        # (obj_id, op_idx) -> {type, elapsed_ns, invocations}; the analogue of
        # the reference's timely/compute introspection logs (SURVEY.md §5).
        # elapsed/invocations are always on (two perf_counter reads per
        # operator dispatch); rows in/out need a device sync per delta, so
        # they are gated by `operator_logging` (enable_operator_logging)
        self.metrics: dict = {}
        self.operator_logging = operator_logging
        # cooperative cancellation: when set (ephemeral peek dataflows), this
        # callable runs between operator dispatches and raises QueryCanceled
        # once the statement's deadline passed or a CancelRequest landed —
        # the reference's PendingPeek cancellation points, but inside the
        # host-orchestrated tick so a runaway peek can't wedge the one core
        self.cancel_check = None

    # -- frontier ----------------------------------------------------------
    @property
    def frontier(self) -> int:
        """Scalar view of the write frontier (u64 max when complete)."""
        return self._frontier.as_scalar((1 << 64) - 1)

    @frontier.setter
    def frontier(self, tick: int) -> None:
        """Advance the frontier; crossing `until` closes the dataflow
        (frontier becomes the EMPTY antichain: nothing more will change)."""
        from .antichain import EMPTY, Antichain

        self._last_complete = max(self._last_complete, int(tick) - 1)
        if self.until and self.until.less_equal(int(tick)):
            self._frontier = EMPTY
        else:
            self._frontier = Antichain.of(int(tick))

    @property
    def frontier_antichain(self):
        return self._frontier

    def is_complete(self) -> bool:
        """True once the frontier is empty — no future update can appear."""
        return self._frontier.is_empty()

    def operator_info(self) -> list:
        """[(obj_id, op_idx, type, elapsed_ns, invocations)] per operator."""
        out = []
        for obj_id, ops, _ref in self.builds:
            for op_i, (node, _ins) in enumerate(ops):
                m = self.metrics.get((obj_id, op_i), {})
                out.append(
                    (
                        obj_id,
                        op_i,
                        type(node).__name__,
                        m.get("elapsed_ns", 0),
                        m.get("invocations", 0),
                    )
                )
        return out

    def operator_rates(self) -> list:
        """[(obj_id, op_idx, type, rows_in, rows_out, retries)] — row counts
        populate only while `operator_logging` is on (zeros otherwise);
        retries are the fused path's overflow-ladder escalations (always 0
        on the host path, which never re-runs an operator)."""
        out = []
        for obj_id, ops, _ref in self.builds:
            for op_i, (node, _ins) in enumerate(ops):
                m = self.metrics.get((obj_id, op_i), {})
                out.append(
                    (
                        obj_id,
                        op_i,
                        type(node).__name__,
                        m.get("rows_in", 0),
                        m.get("rows_out", 0),
                        m.get("retries", 0),
                    )
                )
        return out

    def arrangement_info(self) -> list:
        """[(obj_id, op_idx, name, batches, capacity, records, bytes)].

        Bytes follow the id-deduped owner-charges accounting (see
        batch_nbytes and friends above): a trace shared across dataflows
        contributes its memory exactly once to the cross-dataflow sum.
        Index export traces report as pseudo-operators at op_idx -1.
        """
        out = []
        for obj_id, ops, _ref in self.builds:
            for op_i, (node, _ins) in enumerate(ops):
                rows = node.state_info()
                nbytes = _node_state_bytes(node, rows)
                for (name, nb, cap, rec), b in zip(rows, nbytes):
                    out.append((obj_id, op_i, name, nb, cap, int(rec), int(b)))
        for name, traces in (
            ("index_trace", self.index_traces), ("index_errs", self.index_errs)
        ):
            for idx_id, arr in traces.items():
                for row, b in zip(arr.info_rows(name), _arr_row_nbytes(arr)):
                    out.append((idx_id, -1) + row + (b,))
        return out

    # -- rendering ---------------------------------------------------------
    def _render(self, expr, ops: list):
        """Append (node, input_refs) entries; return a ref (int = op index,
        str = imported/built id). A plan subtree referenced from several
        places (the lowerer reuses node objects, e.g. the default-row pattern
        and reduce collation) renders ONCE and is shared by ref — the
        arrangement-sharing analogue of the reference's CollectionBundle
        reuse (render/context.rs)."""
        e = expr
        memo_key = id(e)
        hit = self._memo.get(memo_key)
        if hit is not None:
            return hit
        ref = self._render_new(e, ops)
        self._memo[memo_key] = ref
        return ref

    def _shareable_gid(self, expr):
        """The collection id of `expr` when it is shareable, else None.
        Sharing keys on IMPORTED collection ids only (source_imports):
        those are stable across dataflows; built-object ids are private."""
        if self.traces is None or not isinstance(expr, lir.Get):
            return None
        return expr.id if expr.id in self.desc.source_imports else None

    def _shared_handle(self, key: tuple, getter):
        """Memoized TraceHandle for trace `key` (one handle per dataflow
        per key — every site of this render shares it), or None when the
        manager has nothing usable. Peek renders (trace_export=False) get
        trusted handles: only a live coordinator may read a trace at the
        importer's as_of (see TraceHandle)."""
        from ..arrangement.trace_manager import TraceHandle

        hit = self._trace_handles.get(key)
        if hit is not None:
            return hit
        tr, imported = getter()
        if tr is None:
            return None
        h = TraceHandle(
            tr, imported, self.desc.as_of, trusted=not self._trace_export
        )
        self._trace_handles[key] = h
        return h

    def _shared_arrangement(self, expr, key_cols: tuple[int, ...]):
        """TraceHandle for an arrangement of `expr` by `key_cols`, or None."""
        gid = self._shareable_gid(expr)
        if gid is None:
            return None
        from ..arrangement.trace_manager import TraceManager

        return self._shared_handle(
            TraceManager.arrangement_key(gid, tuple(key_cols)),
            lambda: self.traces.get_arrangement(
                gid,
                tuple(key_cols),
                self._trace_reader,
                self.desc.as_of,
                export=self._trace_export,
            ),
        )

    def _shared_reduce(self, e: lir.Reduce, in_dtypes: tuple):
        """TraceHandle for a shared accumulable reduce over a Get, or None."""
        gid = self._shareable_gid(e.input)
        if gid is None:
            return None
        from ..arrangement.trace_manager import TraceManager

        return self._shared_handle(
            TraceManager.reduce_key(gid, e.key_cols, e.aggs),
            lambda: self.traces.get_reduce(
                gid,
                e.key_cols,
                e.aggs,
                in_dtypes,
                self._trace_reader,
                self.desc.as_of,
                export=self._trace_export,
            ),
        )

    def _exchanged(self, ref, key_cols, ops: list):
        """In sharded mode, interpose an exchange pact routing by `key_cols`
        (None = whole row) so the downstream stateful operator only ever sees
        the rows its worker owns; identity in single-worker mode."""
        if self.shard is None:
            return ref
        node = ExchangeNode(self.shard, self.shard.alloc_channel(), key_cols)
        ops.append((node, [ref]))
        return len(ops) - 1

    def _render_new(self, expr, ops: list):
        e = expr
        if isinstance(e, lir.Get):
            return e.id
        if isinstance(e, lir.Constant):
            # sharded: exactly one worker emits a literal collection (rows
            # would otherwise be duplicated n_workers times)
            emit = self.shard is None or self.shard.worker == 0
            ops.append((ConstantNode(e, emit=emit), []))
            return len(ops) - 1
        if isinstance(e, lir.Mfp):
            ref = self._render(e.input, ops)
            ops.append((MfpNode(e.mfp), [ref]))
            return len(ops) - 1
        if isinstance(e, lir.Negate):
            ref = self._render(e.input, ops)
            ops.append((NegateNode(), [ref]))
            return len(ops) - 1
        if isinstance(e, lir.Union):
            refs = [self._render(i, ops) for i in e.inputs]
            ops.append((UnionNode(), refs))
            return len(ops) - 1
        if isinstance(e, lir.ArrangeBy):
            h = self._shared_arrangement(e.input, e.key_cols)
            ref = self._render(e.input, ops)
            ref = self._exchanged(ref, e.key_cols, ops)
            if h is not None:
                ops.append((SharedArrangeNode(h, e.key_cols), [ref]))
            else:
                ops.append((ArrangeByNode(e.key_cols), [ref]))
            return len(ops) - 1
        if isinstance(e, lir.Join):
            refs = [self._render(i, ops) for i in e.inputs]
            if isinstance(e.plan, lir.LinearJoinPlan):
                shared = []
                for si, st in enumerate(e.plan.stages):
                    lh = (
                        self._shared_arrangement(e.inputs[0], st.stream_key)
                        if si == 0
                        else None
                    )
                    rh = self._shared_arrangement(e.inputs[si + 1], st.lookup_key)
                    shared.append((lh, rh))
                ops.append(
                    (
                        LinearJoinNode(
                            e.plan, e.closure, shard=self.shard, shared=shared
                        ),
                        refs,
                    )
                )
            else:
                shared = {}
                for path in e.plan.paths:
                    for st in path:
                        key = (st.other_input, st.lookup_key)
                        if key in shared:
                            continue
                        h = self._shared_arrangement(
                            e.inputs[st.other_input], st.lookup_key
                        )
                        if h is not None:
                            shared[key] = h
                ops.append(
                    (
                        DeltaJoinNode(
                            e.plan, e.closure, len(refs), shard=self.shard,
                            shared=shared,
                        ),
                        refs,
                    )
                )
            return len(ops) - 1
        if isinstance(e, lir.Reduce):
            from ..expr.scalar import expr_has_dictfunc

            in_dt = self._infer_dtypes(e.input)
            if (
                not e.distinct
                # sharded: keep the MFP separate so the exchange can route
                # on the reduce's key columns (which index the MFP's output)
                and self.shard is None
                and isinstance(e.input, lir.Mfp)
                and all(a.func in ("sum", "count") for a in e.aggs)
                # string-function MFPs need host tables: keep the MFP as its
                # own eagerly-evaluated node instead of tracing it into the
                # fused reduce tick
                and not any(
                    expr_has_dictfunc(x)
                    for x in list(e.input.mfp.map_exprs) + list(e.input.mfp.predicates)
                )
            ):
                # fuse the feeding MFP into the reduce tick (one dispatch)
                ref = self._render(e.input.input, ops)
                ops.append((FusedMfpReduceNode(e.input.mfp, e, in_dt), [ref]))
                return len(ops) - 1
            ref = self._render(e.input, ops)
            ref = self._exchanged(ref, e.key_cols, ops)
            if e.distinct:
                ops.append((DistinctNode(e.key_cols, in_dt), [ref]))
            else:
                h = self._shared_reduce(e, in_dt)
                if h is not None:
                    ops.append((SharedReduceNode(h), [ref]))
                else:
                    ops.append((ReduceNode(e, in_dt), [ref]))
            return len(ops) - 1
        if isinstance(e, lir.BasicAgg):
            ref = self._render(e.input, ops)
            ref = self._exchanged(ref, e.key_cols, ops)
            ops.append((BasicAggNode(e, self._infer_dtypes(e.input)), [ref]))
            return len(ops) - 1
        if isinstance(e, lir.Threshold):
            ref = self._render(e.input, ops)
            ref = self._exchanged(ref, None, ops)  # co-locate by whole row
            ops.append((ThresholdNode(self._infer_dtypes(e.input)), [ref]))
            return len(ops) - 1
        if isinstance(e, lir.TopK):
            ref = self._render(e.input, ops)
            ref = self._exchanged(ref, e.plan.group_cols, ops)
            if getattr(e, "monotonic", False) and e.plan.limit is not None:
                ops.append((MonotonicTopKNode(e.plan), [ref]))
            else:
                ops.append((TopKNode(e.plan), [ref]))
            return len(ops) - 1
        if isinstance(e, lir.Window):
            ref = self._render(e.input, ops)
            ref = self._exchanged(ref, e.plan.partition_cols, ops)
            ops.append((WindowNode(e.plan), [ref]))
            return len(ops) - 1
        if isinstance(e, lir.LetRec):
            if self.shard is not None:
                # the inner fixpoint would need its own iteration-coordinate
                # channels; out of scope for the v1 sharded plane
                raise NotImplementedError(
                    "WITH MUTUALLY RECURSIVE is not supported on sharded replicas"
                )
            ops.append((LetRecNode(e), list(e.external_ids)))
            return len(ops) - 1
        if isinstance(e, lir.TemporalFilter):
            ref = self._render(e.input, ops)
            self.has_temporal = True
            ops.append((TemporalFilterNode(e), [ref]))
            return len(ops) - 1
        if isinstance(e, lir.FlatMap):
            ref = self._render(e.input, ops)
            ops.append((FlatMapNode(e), [ref]))
            return len(ops) - 1
        raise NotImplementedError(f"render: {type(e).__name__}")

    def _infer_dtypes(self, expr) -> tuple:
        """Column dtypes of a plan expression (for state initialization)."""
        e = expr
        if isinstance(e, lir.Get):
            return self.dtypes[e.id]
        if isinstance(e, lir.Constant):
            return tuple(e.dtypes)
        if isinstance(e, lir.Mfp):
            ins = self._infer_dtypes(e.input)
            cols = list(ins)
            for m in e.mfp.map_exprs:
                cols.append(_expr_dtype(m, cols))
            if e.mfp.projection is not None:
                cols = [cols[i] for i in e.mfp.projection]
            return tuple(cols)
        if isinstance(e, (lir.Negate, lir.Threshold, lir.ArrangeBy)):
            return self._infer_dtypes(e.input)
        if isinstance(e, lir.Union):
            return self._infer_dtypes(e.inputs[0])
        if isinstance(e, lir.TopK):
            return self._infer_dtypes(e.input)
        if isinstance(e, lir.Window):
            return self._infer_dtypes(e.input) + tuple(
                np.dtype(f.out_dtype) for f in e.plan.funcs
            )
        if isinstance(e, lir.Reduce):
            ins = self._infer_dtypes(e.input)
            if e.distinct:
                return tuple(ins[i] for i in e.key_cols)
            return tuple(ins[i] for i in e.key_cols) + tuple(
                agg_out_dtype(a) for a in e.aggs
            )
        if isinstance(e, lir.BasicAgg):
            ins = self._infer_dtypes(e.input)
            return tuple(ins[i] for i in e.key_cols) + (np.dtype(np.int64),)
        if isinstance(e, lir.Join):
            cols = []
            for i in e.inputs:
                cols.extend(self._infer_dtypes(i))
            if e.closure is not None and e.closure.projection is not None:
                base = list(cols)
                for m in e.closure.map_exprs:
                    base.append(_expr_dtype(m, base))
                cols = [base[i] for i in e.closure.projection]
            return tuple(cols)
        if isinstance(e, lir.LetRec):
            return tuple(e.body_dtypes)
        if isinstance(e, lir.TemporalFilter):
            return self._infer_dtypes(e.input)
        if isinstance(e, lir.FlatMap):
            return self._infer_dtypes(e.input) + (np.dtype(np.int64),)
        raise NotImplementedError(f"dtypes: {type(e).__name__}")

    # -- execution ---------------------------------------------------------
    def step(self, tick: int, source_deltas: dict[str, UpdateBatch]) -> dict:
        """Advance to `tick`, flowing the given source deltas through the DAG.

        Returns {exported id: (oks delta, errs delta) or None}.
        """
        import time as _time

        if self.shard is not None:
            self.shard.begin_tick(tick)
        env: dict[str, Delta] = {}
        for sid, batch in source_deltas.items():
            env[sid] = (batch, None)
        results: dict[str, Delta] = {}
        for obj_id, ops, out_ref in self.builds:
            slots: list[Delta] = []
            for op_i, (node, in_refs) in enumerate(ops):
                if self.cancel_check is not None:
                    self.cancel_check()
                ins = [
                    (env.get(r) if isinstance(r, str) else slots[r]) for r in in_refs
                ]
                is_reduce = isinstance(node, _REDUCE_NODES)
                is_topk = isinstance(node, _TOPK_NODES)
                t0 = _time.perf_counter_ns()
                if is_reduce or is_topk:
                    with TRACER.span("reduce.step" if is_reduce else "topk.step"):
                        slots.append(node.step(tick, ins))
                else:
                    slots.append(node.step(tick, ins))
                if slots[-1] is not None:
                    oks, errs = (_bulk_sized(b) for b in slots[-1])
                    slots[-1] = None if oks is None and errs is None else (oks, errs)
                elapsed = _time.perf_counter_ns() - t0
                m = self.metrics.setdefault(
                    (obj_id, op_i),
                    {"type": type(node).__name__, "elapsed_ns": 0, "invocations": 0},
                )
                m["elapsed_ns"] += elapsed
                m["invocations"] += 1
                if is_reduce and node.changed is not None:
                    # per call of a step that had input, never per trace
                    labels = {"dataflow": obj_id, "operator": f"{op_i}:{m['type']}"}
                    _REDUCE_STEP_NS.observe(elapsed, **labels)
                    _REDUCE_CHANGED.inc(node.changed, **labels)
                    _REDUCE_GROUPS.set(node.groups, **labels)
                    outcome = "carried" if node.errs_carried else "empty"
                    _REDUCE_ERR_BATCHES.inc(1, outcome=outcome, **labels)
                    outcome = "collision" if node.collided else "merged"
                    _REDUCE_STATE_MERGES.inc(1, outcome=outcome, **labels)
                elif is_topk and node.regathered is not None:
                    labels = {"dataflow": obj_id, "operator": f"{op_i}:{m['type']}"}
                    _TOPK_STEP_NS.observe(elapsed, **labels)
                    _TOPK_REGATHERED.inc(node.regathered, **labels)
                elif isinstance(node, DeltaJoinNode) and node.stage_rows:
                    op = f"{op_i}:{m['type']}"
                    for path, stage, side, n in node.stage_rows:
                        _JOIN_STAGE_ROWS.inc(n, dataflow=obj_id, operator=op, path=str(path),
                                             stage=str(stage), side=side)
                if self.operator_logging:
                    # row counts need a device sync per delta — gated so the
                    # default tick path does no per-row work (the
                    # enable_operator_logging zero-overhead contract)
                    rin = sum(
                        int(d[0].count()) for d in ins if d is not None and d[0] is not None
                    )
                    out_d = slots[-1]
                    rout = (
                        int(out_d[0].count())
                        if out_d is not None and out_d[0] is not None
                        else 0
                    )
                    m["rows_in"] = m.get("rows_in", 0) + rin
                    m["rows_out"] = m.get("rows_out", 0) + rout
            out = env.get(out_ref) if isinstance(out_ref, str) else slots[out_ref]
            if self.until and out is not None:
                out = (
                    _truncate_until(out[0], self.until.elements[0]),
                    _truncate_until(out[1], self.until.elements[0]),
                )
            env[obj_id] = out
            results[obj_id] = out
        for idx_id, (obj_id, _k) in self.desc.index_exports.items():
            d = results.get(obj_id)
            if d is not None:
                oks, errs = d
                if oks is not None:
                    self.index_traces[idx_id].insert(oks)
                if errs is not None:
                    self.index_errs[idx_id].insert(errs)
        for sink_id, obj_id in self.desc.sink_exports.items():
            d = results.get(obj_id)
            if d is not None and d[0] is not None:
                self.sink_outputs[sink_id].append((tick, d[0]))
        self.frontier = tick + 1
        return results

    def peek(
        self,
        index_id: str,
        at: Optional[int] = None,
        byte_budget: int | None = None,
    ) -> list[tuple]:
        """Snapshot read of an exported index at time `at` (default: latest
        complete time). The analogue of PendingPeek::Index cursor scans
        (src/compute/src/compute_state.rs:1273).

        Frontier discipline (the reference's since ≤ at < upper peek
        invariant, src/adapter/src/coord.rs:22-66): a peek below `since`
        reads compacted history whose times were forwarded — the snapshot
        would be silently partial, so it errors; a peek at/after the write
        frontier reads incomplete data, so it errors (the controller only
        issues peeks once ProcessTo has advanced past `at`)."""
        if at is None:
            at = (
                self._last_complete
                if self._frontier.is_empty()
                else self.frontier - 1
            )
        since = self.index_traces[index_id].since
        if at < since:
            raise RuntimeError(
                f"peek at time {at} is below the since frontier {since}: "
                "that history has been compacted away"
            )
        if self._frontier and at >= self.frontier:
            raise RuntimeError(
                f"peek at time {at} is not beyond the write frontier "
                f"{self.frontier}: the result would be incomplete"
            )
        acc: dict[tuple, int] = {}
        for data, _t, d in self.index_errs[index_id].rows_host(at):
            acc[data] = acc.get(data, 0) + d
        if any(v > 0 for v in acc.values()):
            raise RuntimeError(peek_error_message(index_id, acc))
        out: dict[tuple, int] = {}
        for data, _t, d in self.index_traces[index_id].rows_host(at):
            out[data] = out.get(data, 0) + d
        return materialize_counts(out, index_id, byte_budget=byte_budget)

    def compact(self, since: int) -> None:
        for _obj, ops, _ref in self.builds:
            for node, _ins in ops:
                node.compact(since)
        for arr in self.index_traces.values():
            arr.compact(since)
        for arr in self.index_errs.values():
            arr.compact(since)
        if self.traces is not None and self._trace_reader is not None:
            # advance this reader's since holds; each shared trace compacts
            # to the minimum over its remaining holds (AllowCompaction under
            # the reader-held protocol)
            self.traces.downgrade(self._trace_reader, since)


def _truncate_until(b: Optional[UpdateBatch], until: int) -> Optional[UpdateBatch]:
    """Suppress updates at times ≥ until (they are not needed by anyone —
    reference dataflows.rs `until` semantics). Rows keep their slots with
    diff 0 / PAD hash, the engine-wide dead-row discipline."""
    if b is None:
        return None
    from ..repr.batch import PAD_TIME
    from ..repr.hashing import PAD_HASH

    # `until` is a host u64-domain bound; clamp to PAD_TIME so an unbounded
    # until keeps every live row (live times are < PAD_TIME by construction)
    keep = b.times < np.uint32(min(int(until), int(PAD_TIME)))
    return UpdateBatch(
        jnp.where(keep, b.hashes, PAD_HASH),
        b.keys,
        b.vals,
        jnp.where(keep, b.times, PAD_TIME),
        jnp.where(keep, b.diffs, 0),
    )


def _expr_dtype(expr, col_dtypes):
    """Static result dtype of a scalar expr given input column dtypes."""
    from ..expr import scalar as s

    if isinstance(expr, s.Column):
        return np.dtype(col_dtypes[expr.index])
    if isinstance(expr, s.Literal):
        return np.dtype(expr.dtype)
    if isinstance(expr, s.DictFunc):
        return np.dtype(np.int8) if expr.out == "bool" else np.dtype(np.int64)
    if isinstance(expr, s.CallUnary):
        if expr.func in ("cast_int64", "extract_year", "extract_month", "extract_day"):
            return np.dtype(np.int64)
        if expr.func in s._DATE_UNARY:
            return np.dtype(np.int64)
        if expr.func in ("cast_int32",):
            return np.dtype(np.int32)
        if expr.func in ("cast_float", "sqrt", "round_half_away"):
            return np.dtype(np.float32)
        if expr.func in s._FLOAT_UNARY:
            return np.dtype(np.float32)
        if expr.func == "is_true":
            return np.dtype(np.bool_)
        if expr.func in ("not", "is_null", "is_not_null"):
            return np.dtype(np.int8)  # stored truth values (nullable bool)
        return _expr_dtype(expr.expr, col_dtypes)
    if isinstance(expr, s.CallBinary):
        if expr.func in ("eq", "ne", "lt", "lte", "gt", "gte", "and", "or"):
            return np.dtype(np.int8)
        lt_ = _expr_dtype(expr.left, col_dtypes)
        rt = _expr_dtype(expr.right, col_dtypes)
        return np.promote_types(lt_, rt)
    if isinstance(expr, s.CallVariadic):
        if expr.func in ("and", "or"):
            return np.dtype(np.int8)
        if expr.func == "if":
            return np.promote_types(
                _expr_dtype(expr.exprs[1], col_dtypes),
                _expr_dtype(expr.exprs[2], col_dtypes),
            )
        dts = [_expr_dtype(e, col_dtypes) for e in expr.exprs]
        out = dts[0]
        for d in dts[1:]:
            out = np.promote_types(out, d)
        return out
    raise TypeError(f"not a ScalarExpr: {expr!r}")


def render_dataflow(
    desc: lir.DataflowDescription,
    *,
    fused: bool = False,
    exchange_backend: str = "auto",
    mesh=None,
    caps=None,
    traces=None,
    trace_reader: str | None = None,
    operator_logging: bool = False,
    snap_rows: int = 0,
):
    """Render a DataflowDescription under the exchange-backend policy.

    The ONE rendering decision point shared by the coordinator (local
    replicas) and clusterd (remote whole-replica mode): `exchange_backend`
    (host/device/auto, the dyncfg) picks the exchange plane via
    `devicemesh.resolve_exchange_mesh`, then the fused single-program render
    is attempted when requested (or implied by a device mesh — the device
    plane only exists inside the fused tick) and the host-orchestrated
    operator graph is the fallback for plans fused can't express
    (the rendering-choice analogue of ENABLE_MZ_JOIN_CORE).

    `snap_rows` pre-sizes fused delta capacity so a hydration tick does not
    ladder through doubling retries.
    """
    from ..parallel.devicemesh import resolve_exchange_mesh

    dmesh = resolve_exchange_mesh(exchange_backend, mesh)
    if fused or exchange_backend == "device":
        from .fused import FusedDataflow, FusedUnsupported

        try:
            df = FusedDataflow(
                desc,
                caps=caps,
                mesh=dmesh,
                traces=traces,
                operator_logging=operator_logging,
            )
            if snap_rows:
                df.ensure_delta_capacity(int(snap_rows))
            return df
        except FusedUnsupported as e:
            # the fused render was asked for and is not what the view gets:
            # say so, once per view, where an operator reads it
            _log.warn(
                "fused render unsupported for this plan; rendering on the host",
                reason=str(e),
                objects=[bd.id for bd in desc.objects_to_build],
            )
    return Dataflow(
        desc,
        traces=traces,
        trace_reader=trace_reader,
        operator_logging=operator_logging,
    )
