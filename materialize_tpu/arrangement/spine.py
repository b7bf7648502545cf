"""Arrangements: device-resident indexed state, maintained as an LSM spine.

The TPU re-design of differential's `Spine`/`TraceReader` and the reference's
`mz-row-spine` (src/row-spine/src/lib.rs:9-28): an arrangement is a list of
consolidated, hash-sorted UpdateBatches of geometrically decreasing capacity.

- batch build   = radix/lex sort by (hash, key, val, time)  [ops.consolidate]
- batch merge   = concat + consolidate (one fused XLA program)
- cursor lookup = vectorized binary search over the hash column [ops.join]

Merge scheduling is driven by static capacities (powers of two); the one
host read an insert makes is the delta's live row count (below), and
re-bucketing reads live counts back when it shrinks capacity.

**The head.** Every batch capacity is a family of XLA programs (the merge,
and every join that probes the batch), so a spine that grew each delta
through d, 2d, 4d, ... asked for new programs at nearly every insert. The
small end of that ladder is one batch of FIXED capacity instead: the head,
always the last element of `batches`, so readers see one more batch.

- A delta is sized by the rows it holds (one host read), not by the capacity
  its producer left it at: an operator's output keeps the summed capacity of
  its inputs (at TPC-H SF1 the Q3 view's delta is 32,768 rows wide for some
  260 rows, its error delta 131,072 wide for none), and merging costs by
  capacity. A delta with no rows is not inserted.
- A delta whose rows fill bucket d, arriving at a headless, non-empty
  arrangement, starts a head of capacity T = HEAD_RATIO x d (x 2d where
  the delta's capacity is wider than its rows: an operator's next output
  may hold somewhat more). Later deltas merge into it with ONE program,
  `merge_consolidate` at (T, d) with the output held at T (a smaller delta
  is padded to d; a larger one of bucket d' <= T/2 goes in d rows at a
  time: a handful of rows crosses its bucket by chance from tick to tick,
  and a (T, d') program of its own costs the chip's compiler seconds in
  whichever tick first meets it).
- Bound: the host keeps `head_bound`, the sum of the row counts merged into
  the head since it was empty. A merge never creates rows, so the head's
  live rows never exceed it, and while it stays <= T, truncating the merge's
  T + d output rows to T drops only padding — no device read needed.
- Spill: when the next delta would take `head_bound` past T, the head joins
  the spine as it is, at capacity T (the join programs that probe it are the
  head's own), and a new head starts from the delta. The geometric rule then
  runs among spine batches only, whose smallest level is T.
- What a head cannot help goes to the spine as before, after spilling the
  head so order and `since` handling are unchanged: the first batch of an
  arrangement (a hydration snapshot), a delta whose bucket exceeds T/2, and
  a delta whose head would be larger than the spine it fronts (a bulk load;
  this also keeps a head from more than doubling an arrangement's memory,
  which is the envelope the geometric spine reaches by itself before a full
  merge). The smallest head there is, HEAD_RATIO x MIN_CAP rows, is always
  allowed, so a table filled row by row has one from its second insert.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp

from ..obs import metrics as obs_metrics
from ..ops.consolidate import advance_times, compact_to, consolidate, merge_consolidate
from ..repr.batch import MIN_CAP, UpdateBatch, bucket_cap, device_time_scalar
from ..repr.hashing import hash_columns

# Head capacity over the bucket of the delta that starts it. On a TPU v5e the
# (T, d) merge of a 16,384-row, 10-column delta takes 19 / 54 / 147 ms at
# ratios 4 / 16 / 64 (PERF.md §6, PR 33; 34 / 112 / 378 ms before
# `merge_perm`, when the head's rows were searched into the delta too): the
# price of a merge against how often a head spills; 4 against 16 is PERF.md's
# open question 0e.
HEAD_RATIO = 16

_HEAD_MERGES = obs_metrics.REGISTRY.counter(
    "mzt_arrangement_head_merges_total",
    "deltas merged into an arrangement's fixed-capacity head batch",
)
_HEAD_SPILLS = obs_metrics.REGISTRY.counter(
    "mzt_arrangement_head_spills_total",
    "head batches appended to the spine (full, or displaced by a larger delta)",
)
_HEAD_BYPASS = obs_metrics.REGISTRY.counter(
    "mzt_arrangement_head_bypass_total",
    "deltas sent straight to the spine (first batch, bulk load, oversize)",
)


_live_rows = jax.jit(UpdateBatch.count)
# pad, or cut (sound where the rows beyond are padding: arranged batches
# keep their live rows in front), as one program per shape
_resized = jax.jit(UpdateBatch.with_capacity, static_argnums=1)


@partial(jax.jit, static_argnums=2)
def _rows_from(batch: UpdateBatch, lo, n: int) -> UpdateBatch:
    """Rows [lo, lo + n) of a batch: one program whatever `lo` is."""
    return jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_slice_in_dim(x, lo, n), batch
    )


def sized(
    batch: UpdateBatch | None, rows: int | None = None, slack: int = HEAD_RATIO
) -> UpdateBatch | None:
    """A batch sized by the rows it holds: its live rows, in their order, at
    their pow2 bucket where it is `slack` times wider or more, else as it is
    (a batch nearer its bucket keeps the capacity its producer repeats).
    `rows` is an upper bound of the live rows that the caller holds on the
    host; without one it costs ONE host read, and an empty batch is None."""
    if batch is None:
        return None
    if rows is None:
        rows = int(_live_rows(batch))
        if rows == 0:
            return None
    d = bucket_cap(rows)
    return compact_to(batch, d)[0] if slack * d <= batch.cap else batch


def arrange_batch(
    batch: UpdateBatch, key_cols: tuple[int, ...], compact: bool = True
) -> UpdateBatch:
    """Key a raw batch by the given val-column indices and canonicalize it.

    The analogue of the ArrangeBy LIR operator's batch construction
    (reference: src/compute/src/render.rs:1303). Key columns are *copied*
    into `keys` (vals stay the full row) and the hash is recomputed.

    `compact=False` skips the compaction sort (see ops/consolidate.py):
    right for probe streams and LSM-insert deltas inside fused ticks, which
    never capacity-truncate the batch; spine contents keep the default.
    """
    keys = tuple(batch.vals[i] for i in key_cols)
    if keys:
        hashes = hash_columns(keys)
        # preserve padding: dead rows keep PAD via diff==0 after consolidate
        hashes = jnp.where(batch.live, hashes, batch.hashes)
    else:
        hashes = jnp.where(batch.live, jnp.zeros_like(batch.hashes), batch.hashes)
    keyed = UpdateBatch(hashes, keys, batch.vals, batch.times, batch.diffs)
    return consolidate(keyed, compact=compact)


@dataclass
class Arrangement:
    """Host handle to spine state. `key_cols` indexes into the row (val) columns.

    `holds` is the reader-held compaction ledger (the persist leased-reader
    shape, host-side): a shared arrangement may be probed by several
    dataflows, and `allow_compaction` only advances `since` to the minimum
    over live holds — releasing a hold (DROP of a reader) re-arms compaction
    up to the next-slowest reader. Private arrangements never register holds
    and keep the plain `compact` path.

    `head_bound` > 0 says `batches[-1]` is the head (module docstring): it
    is the sum of the row counts merged into it, an upper bound on its rows.
    """

    key_cols: tuple[int, ...]
    batches: list[UpdateBatch] = field(default_factory=list)
    since: int = 0  # logical compaction frontier
    holds: dict = field(default_factory=dict)  # reader id -> held since
    head_bound: int = 0

    @property
    def head(self) -> UpdateBatch | None:
        return self.batches[-1] if self.head_bound else None

    def insert(self, delta: UpdateBatch, already_keyed: bool = False) -> None:
        """Add a delta batch (raw, keyed on the fly): into the head where one
        can take it, else onto the spine, restoring the merge invariant."""
        b = delta if already_keyed else arrange_batch(delta, self.key_cols)
        # The one host read: a delta is sized by the rows it holds, not by
        # the capacity its producer left it at (an operator's output keeps
        # the summed capacity of its inputs: the view's own delta at SF1 is
        # 32,768 rows wide for some 260 rows, its error delta 131,072 for
        # none), and a delta with no rows is not inserted at all.
        n = int(_live_rows(b))
        if n == 0:
            return
        d = bucket_cap(n)
        head = self.head
        oversize = head is not None and 2 * d > head.cap
        if head is not None and (oversize or self.head_bound + n > head.cap):
            self._spill()
            head = None
        # a new head gets one bucket of slack where the producer's capacity
        # allows it: an ingest batch is built at its bucket and keeps it, the
        # next output of an operator may hold somewhat more rows than this
        slot = min(2 * d, bucket_cap(b.cap))
        room = max(self.total_cap(), HEAD_RATIO * MIN_CAP) if self.batches else 0
        if head is None and not oversize and HEAD_RATIO * slot <= room:
            # it starts empty and takes the delta through the same (T, d)
            # program every later merge runs
            head = UpdateBatch.empty(
                HEAD_RATIO * slot,
                tuple(k.dtype for k in b.keys),
                tuple(v.dtype for v in b.vals),
            )
            self.batches.append(head)
        if head is None:
            _HEAD_BYPASS.inc()
            self.batches.append(b)
            self._maintain()
            return
        # one program per head, (T, d) with d the head's own: a smaller delta
        # is cut or padded to d, a larger one goes in d rows at a time (an
        # arranged batch keeps its live rows in front and in order, so every
        # piece is a canonical batch); the output is held at T
        step = head.cap // HEAD_RATIO
        if d <= step:
            pieces = [_resized(b, step)]
        else:
            whole = b if b.cap >= d else _resized(b, d)  # the last piece ends by d
            pieces = [_rows_from(whole, lo, step) for lo in range(0, n, step)]
        for piece in pieces:
            self.batches[-1] = merge_consolidate(
                self.batches[-1],
                piece,
                since=device_time_scalar(self.since),
                out_cap=head.cap,
            )
        self.head_bound += n
        _HEAD_MERGES.inc()

    def _spill(self) -> None:
        """The head becomes a spine batch as it is, at its own capacity."""
        if self.head_bound:
            self.head_bound = 0
            _HEAD_SPILLS.inc()
            self._maintain()

    # -- reader-held compaction (shared-trace protocol) ---------------------
    def hold(self, reader: str, since: int) -> None:
        """Register (or re-pin) `reader`'s since hold; compaction can never
        advance past the minimum live hold while the reader is registered."""
        self.holds[reader] = int(since)

    def downgrade_hold(self, reader: str, since: int) -> None:
        """Advance one reader's hold (holds only ever move forward)."""
        if reader in self.holds:
            self.holds[reader] = max(self.holds[reader], int(since))

    def release_hold(self, reader: str) -> None:
        """Drop a reader's hold and re-arm compaction to the remaining
        minimum (the DROP-releases-hold half of the sharing protocol).
        A reader with no hold here is a no-op — it must not advance since
        on an arrangement it never read."""
        if self.holds.pop(reader, None) is None:
            return
        if self.holds:
            self.compact(min(self.holds.values()))

    def allow_compaction(self, since: int) -> None:
        """Advance `since`, but never past the minimum live reader hold."""
        if self.holds:
            since = min(since, min(self.holds.values()))
        self.compact(since)

    def _maintain(self) -> None:
        # Merge while the tail batch is at least half the size of its
        # predecessor (geometric spine, amortized O(log) merges per insert).
        # Spine batches only: callers spill the head first.
        while len(self.batches) >= 2 and (
            self.batches[-1].cap * 2 >= self.batches[-2].cap
        ):
            b = self.batches.pop()
            a = self.batches.pop()
            # spine batches are consolidate outputs (canonical order), so the
            # O(n) searchsorted merge replaces the full re-sort
            self.batches.append(
                merge_consolidate(
                    a,
                    b,
                    since=device_time_scalar(self.since),
                    out_cap=bucket_cap(a.cap + b.cap),
                )
            )

    def compact(self, since: int) -> None:
        """Advance the logical compaction frontier (AllowCompaction;
        reference: src/compute/src/compute_state.rs:732)."""
        self.since = max(self.since, since)

    def rebucket(self) -> None:
        """Shrink capacities to fit live counts (host sync; call occasionally)."""
        self.head_bound = 0  # the head is one more batch to shrink
        new = []
        for b in self.batches:
            n = int(b.count())
            cap = bucket_cap(n)
            if cap < b.cap:
                b = consolidate(b).with_capacity(cap)
            new.append(b)
        self.batches = [b for b in new]
        self._maintain()

    def merged(self) -> UpdateBatch:
        """One consolidated batch of the full contents (snapshot reads/peeks)."""
        if not self.batches:
            return UpdateBatch.empty(8)
        out = self.batches[0]
        for b in self.batches[1:]:
            out = UpdateBatch.concat(out, b)
        return consolidate(advance_times(out, self.since))

    def rows_host(self, at: int | None = None) -> list[tuple]:
        """Consolidated (data, time, diff) rows via the HOST path.

        Peeks hit spines whose batch count/capacities change every tick; the
        device `merged()` would recompile per shape. This path device_gets the
        live rows and consolidates with the native C++ kernel instead — zero
        XLA involvement (the PendingPeek cursor-scan analogue,
        compute_state.rs:1129).
        """
        import numpy as np

        from ..utils.native import consolidate_host

        parts: list[dict] = []
        ncols = None
        for b in self.batches:
            h = b.to_host()
            if len(h["times"]) == 0:
                continue
            ncols = len(h["vals"])
            part = {f"c{i}": np.asarray(c) for i, c in enumerate(h["vals"])}
            part["times"] = np.asarray(h["times"])
            part["diffs"] = np.asarray(h["diffs"])
            parts.append(part)
        if not parts:
            return []
        cols = {
            k: np.concatenate([p[k] for p in parts]) for k in parts[0]
        }
        since = np.uint64(self.since)
        cols["times"] = np.maximum(cols["times"], since)
        if at is not None:
            mask = cols["times"] <= np.uint64(at)
            cols = {k: v[mask] for k, v in cols.items()}
        out = consolidate_host(cols)
        n = len(out["times"])
        # bulk column→list conversion (C loop) instead of per-cell .item();
        # float NaN (the float NULL sentinel) becomes None so NULL rows
        # accumulate/compare correctly in host dicts
        col_lists = []
        for j in range(ncols):
            c = out[f"c{j}"]
            lst = c.tolist()
            if c.dtype.kind == "f":
                lst = [None if x != x else x for x in lst]
            col_lists.append(lst)
        times_l = out["times"].tolist()
        diffs_l = out["diffs"].tolist()
        if not col_lists:
            return [((), int(t), int(d)) for t, d in zip(times_l, diffs_l)]
        return [
            (data, int(t), int(d))
            for data, t, d in zip(zip(*col_lists), times_l, diffs_l)
        ]

    def count(self) -> int:
        return sum(int(b.count()) for b in self.batches)

    def total_cap(self) -> int:
        return sum(b.cap for b in self.batches)

    def info_rows(self, name: str) -> list[tuple]:
        """`state_info` rows (name, batches, capacity, records): the spine,
        and the head as a row of its own so mz_arrangement_sizes shows it."""
        spine = self.batches[:-1] if self.head_bound else self.batches
        rows = [
            (name, len(spine), sum(b.cap for b in spine), sum(int(b.count()) for b in spine))
        ]
        if self.head_bound:
            rows.append((f"{name}:head", 1, self.head.cap, int(self.head.count())))
        return rows


def _host_value(v):
    """Python value of one host scalar; float NaN (the float NULL sentinel)
    becomes None so NULL rows accumulate/compare correctly in host dicts
    (two NaN objects are never equal in Python)."""
    x = v.item()
    if isinstance(x, float) and x != x:
        return None
    return x
