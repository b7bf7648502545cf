"""Push SUBSCRIBE: per-subscriber *cursors* over shared per-collection frames.

The reference streams SUBSCRIBE updates from a dedicated dataflow sink
(src/compute/src/sink/subscribe.rs) into the adapter's pending-subscribe
machinery; here the coordinator's `_apply_writes` plays the sink role — at
every commit tick it publishes the tracked collection's consolidated update
triples ONCE into the collection's shared `Channel` (egress/fanout.py), and
each `Subscription` is a cursor into that ring. A frontend (pgwire COPY out,
HTTP NDJSON/poll, or the serve/ reactor) drains the cursor WITHOUT holding
the coordinator lock; slow readers hold a cursor position, not a queue copy.

Backpressure contract: a consumer whose pending backlog of *updates since
it subscribed* exceeds `subscribe_queue_depth` messages — or whose cursor
falls off the ring's `fanout_ring_ticks` retention window — is *shed*: the
subscription flips to `shed` and the next drain raises
`SubscriptionOverflow` (SQLSTATE 53400), rather than letting one stalled
client pin unbounded history (the overload-protection stance of
adapter/overload.py, applied to egress). The subscriber's own snapshot
preamble is not part of that backlog: its size is the view's, fixed when the
SUBSCRIBE is accepted, and says nothing about how fast the client reads — a
view with more rows than the depth must still be subscribable.

Threading: producer is the coordinator (under the global command lock),
consumers are frontend threads / the reactor (explicitly NOT under it).
Per-subscription state is guarded by the subscription's own condition
variable; shared ring state by the channel's mutex. Lock order is
subscription-cv → channel-mutex; waits are bounded so consumers always
observe cancel/teardown promptly.

A `Subscription` constructed without a channel (unit tests, ad-hoc feeds)
still supports the historical `publish()` API: those entries live in a
private per-subscriber preamble deque — which is also how each subscriber's
snapshot (emitted at its own `as_of`, inherently per-subscriber) rides in
front of the shared ticks.
"""

from __future__ import annotations

import threading
from collections import deque

from ..errors import SubscriptionOverflow
from ..obs import metrics as obs_metrics
from .fanout import _DELIVERED, _ENCODED, _UPDATES, ENCODERS, Frame, FrameEntry

# mzt_egress_*: the egress plane's /metrics families (obs satellite). The
# names are asserted present by the metrics-coherence REQUIRED check only
# transitively — but every overload `.bump` in this package is picked up by
# that rule's source grep, so shed accounting is lint-enforced observable.
# (_UPDATES lives in fanout.py now: the channel bulk-accounts it per tick.)
_SHEDS = obs_metrics.REGISTRY.counter(
    "mzt_egress_subscribe_sheds_total",
    "subscriptions shed because their bounded queue overflowed (53400)",
)


class Subscription:
    """One client's tap on a collection: a cursor over the shared frame ring
    plus a private preamble (snapshot rows, standalone publishes).

    Messages are `(ts, progressed, diff, row)` tuples; `progressed=True`
    rows carry no data (`diff=0, row=None`) and mark that every update with
    time < ts has been delivered (the SUBSCRIBE … WITH (PROGRESS) rows).

    States: `active` → one of `shed` (backlog overflow or retention loss,
    53400), `cancelled` (client cancel/disconnect, 57014/57P05 decided by
    the frontend), or `dropped` (the underlying object went away; the
    stream ends cleanly after the pending prefix drains).
    """

    def __init__(
        self,
        sub_id: str,
        gid: str,
        object_name: str,
        pq,
        columns: tuple,
        snapshot: bool = True,
        progress: bool = False,
        max_depth: int = 4096,
        hidden_mv: str | None = None,
        channel=None,
        user: str = "anonymous",
    ):
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self.sub_id = sub_id
        self.gid = gid
        self.object_name = object_name
        self.pq = pq  # planned query: row decode schema (coordinator-owned)
        self.columns = tuple(columns)
        self.snapshot = bool(snapshot)
        self.progress = bool(progress)
        self.max_depth = int(max_depth)
        self.hidden_mv = hidden_mv  # name of the _sub_N MV backing an ad-hoc query
        self.user = user  # per-tenant admission accounting (53300 budgets)
        # read frontier: updates with time < frontier have been enqueued;
        # _drive_compaction holds `since` below it (the read-hold contract).
        # Shared ticks advance the CHANNEL's frontier (one write per tick,
        # not one per subscriber); the property below folds it in.
        self.frontier = 0
        self.state = "active"
        self.delivered = 0  # messages handed to the consumer
        self.shed_count = 0
        # private preamble: (FrameEntry, deliver_progress, snapshot) triples
        # owned by THIS subscriber — snapshot rows and compat `publish()`
        # entries
        self._private: deque = deque()
        self._poff = 0  # updates consumed in the head private entry
        self._priv_pending = 0  # undelivered private messages
        self._snap_pending = 0  # ... of which snapshot rows (not backlog)
        self._shed_reason: str | None = None
        # shared-ring cursor: next entry seq + updates consumed within it
        self.channel = channel
        self._off = 0
        self._seq = channel.register(self) if channel is not None else 0

    @property
    def frontier(self) -> int:
        """Effective read frontier. The coordinator advances the channel's
        frontier once per tick for ALL cursors; the private `_frontier`
        covers subscribe-time state and channelless subscriptions."""
        ch = self.channel
        return max(self._frontier, ch.frontier) if ch is not None else self._frontier

    @frontier.setter
    def frontier(self, v: int) -> None:
        self._frontier = int(v)

    # -- producer side (coordinator tick, holds the command lock) -------------
    def publish(
        self, updates: list, progress_ts: int | None = None, snapshot: bool = False
    ) -> bool:
        """Enqueue one tick's decoded updates `[(ts, diff, row)]` (plus an
        optional progress marker) into the PRIVATE preamble. Returns False
        when the subscription is no longer active — the caller should tear
        it down. Shared-ring ticks arrive via the channel instead.

        `snapshot=True` marks the subscriber's own snapshot: it is delivered
        whatever its size and never counts against `max_depth`."""
        with self._cv:
            if self.state != "active":
                return False
            n = len(updates) + (1 if progress_ts is not None else 0)
            if n == 0:
                return True
            if (
                not snapshot
                and self.max_depth > 0
                and self._backlog_locked() + n > self.max_depth
            ):
                self._shed_locked()
                return False
            msgs = tuple((int(ts), False, int(d), row) for ts, d, row in updates)
            entry = FrameEntry(
                -1, int(progress_ts or (msgs[0][0] if msgs else 0)), msgs,
                progress_ts, 0, 0, columns=self.columns,
            )
            # private entries deliver their progress marker unconditionally:
            # the publisher asked for it explicitly
            self._private.append((entry, progress_ts is not None, snapshot))
            self._priv_pending += n
            if snapshot:
                self._snap_pending += n
            if n:
                _UPDATES.inc(len(updates))
                self._cv.notify_all()
            return True

    def shared_tick_exact(self, entry: FrameEntry) -> tuple:
        """The exact (locked) per-cursor tick check, run only during the
        channel's rare depth sweep — the common tick path is the O(1) floor
        test in `Channel.shared_tick`. Returns `(keep, eff)`: keep=False
        when the subscription must be torn down (shed by the backlog bound,
        shed by retention loss, or closed under us); `eff` is this cursor's
        effective position, fed back into the channel's floor."""
        with self._cv:
            if self.state != "active" or self.channel is None:
                return False, 0
            ch = self.channel
            if self._seq < ch.base_seq:
                # the ring's retention window moved past this cursor: data
                # is provably lost, so the gap-free contract forces a shed
                self._shed_locked(
                    f"subscription {self.sub_id} on {self.object_name} shed: "
                    "cursor fell off the fan-out ring's retention window "
                    "(fanout_ring_ticks)"
                )
                return False, 0
            if self.max_depth > 0 and self._backlog_locked() > self.max_depth:
                self._shed_locked()
                return False, 0
            before_u, before_p = ch.cum_before(self._seq)
            # positional consumption (counting progress markers whether or
            # not this cursor delivers them) minus the counted private
            # backlog: a pessimistic position, so head - floor always bounds
            # depth
            backlog = self._priv_pending - self._snap_pending
            return True, before_u + self._off + before_p - backlog

    def close(self, state: str = "dropped") -> None:
        """Terminal transition (idempotent): wakes blocked consumers. The
        cursor detaches from the shared ring; undelivered shared messages
        are captured (by reference — entries are immutable) so a `dropped`
        stream still ends with its clean gap-free prefix."""
        with self._cv:
            if self.state == "active":
                self.state = state
                self._capture_shared_locked()
            ch = self.channel
            self.channel = None
            self._cv.notify_all()
        if ch is not None:
            ch.unregister(self)
            # consumers may be parked on the channel's shared condition —
            # wake them so they observe the terminal state promptly
            ch.notify_waiters()

    # -- consumer side (frontend thread, does NOT hold the command lock) ------
    def pop(self, timeout: float = 0.1):
        """One message, or None after `timeout`/on clean end. Raises
        `SubscriptionOverflow` (53400) once the subscription was shed; the
        caller distinguishes clean end from timeout via `state`."""
        with self._cv:
            msg = self._next_locked()
            waiter = (
                self._tick_waiter_locked()
                if msg is None and self.state == "active" and timeout > 0
                else None
            )
            if waiter is None:
                return self._pop_result_locked(msg)
        waiter(timeout)
        with self._cv:
            return self._pop_result_locked(self._next_locked())

    def pop_frame(self, fmt: str, timeout: float = 0.1):
        """One pre-encoded `Frame` (the remainder of one tick entry), or
        None after `timeout`/on clean end. Shared-ring frames reuse the
        channel's encode-once cache; private preamble frames (snapshots)
        are encoded per-subscriber. Raises `SubscriptionOverflow` (53400)
        once shed, like `pop`."""
        with self._cv:
            fr = self._next_frame_locked(fmt)
            waiter = (
                self._tick_waiter_locked()
                if fr is None and self.state == "active" and timeout > 0
                else None
            )
            if waiter is None:
                return self._frame_result_locked(fr, fmt)
        waiter(timeout)
        with self._cv:
            return self._frame_result_locked(self._next_frame_locked(fmt), fmt)

    def _tick_waiter_locked(self):
        """A callable parking the consumer until new data may exist.
        Cursors park on the CHANNEL's single condition — the producer
        notifies one cv per channel per tick, not one per subscriber —
        while channelless subscriptions fall back to the private cv.
        Called with `_cv` held; the wait itself runs without it."""
        ch = self.channel
        if ch is None:
            return self._wait_private
        return lambda t, c=ch, s=self._seq: c.wait_for_tick(s, t)

    def _wait_private(self, timeout: float) -> None:
        with self._cv:
            # re-check under the lock: a publish/close that landed between
            # the caller's drain and this wait must not be slept through
            if self._priv_pending == 0 and self.state == "active":
                self._cv.wait(timeout)

    def _pop_result_locked(self, msg):
        if msg is not None:
            self.delivered += 1
            return msg
        if self.state == "shed":
            raise SubscriptionOverflow(self._overflow_msg_locked())
        return None

    def _frame_result_locked(self, fr, fmt: str):
        if fr is not None:
            self.delivered += fr.count
            _DELIVERED.inc(1, format=fmt)
            return fr
        if self.state == "shed":
            raise SubscriptionOverflow(self._overflow_msg_locked())
        return None

    def drain(self) -> list:
        """Everything pending right now (the HTTP poll path)."""
        with self._cv:
            if self.state == "shed":
                raise SubscriptionOverflow(self._overflow_msg_locked())
            msgs = []
            while True:
                m = self._next_locked()
                if m is None:
                    break
                msgs.append(m)
            if self.state == "shed":  # retention loss discovered mid-walk
                raise SubscriptionOverflow(self._overflow_msg_locked())
            self.delivered += len(msgs)
            return msgs

    def queue_depth(self) -> int:
        with self._cv:
            if self.state == "shed":
                return 0  # a shed client's backlog is dropped, as before
            return self._depth_locked()

    # -- internals (all hold self._cv; may take the channel mutex inside) -----
    def _backlog_locked(self) -> int:
        """What `max_depth` bounds: everything undelivered but the
        subscriber's own snapshot."""
        return self._depth_locked() - self._snap_pending

    def _depth_locked(self) -> int:
        depth = self._priv_pending
        ch = self.channel
        if ch is not None:
            head_u, head_p = ch.head_totals()
            before_u, before_p = ch.cum_before(self._seq)
            depth += head_u - before_u - self._off
            if self.progress:
                depth += head_p - before_p
        return depth

    def _shed_locked(self, reason: str | None = None) -> None:
        self.state = "shed"
        self.shed_count += 1
        self._shed_reason = reason
        self._private.clear()  # a shed client never sees a partial tick
        self._priv_pending = 0
        self._snap_pending = 0
        self._poff = 0
        _SHEDS.inc()
        self._cv.notify_all()

    def _next_locked(self):
        if self.state == "shed":
            return None
        # private preamble first: snapshot rows precede the shared ticks
        while self._private:
            entry, deliver_progress, snapshot = self._private[0]
            if self._poff < len(entry.updates):
                msg = entry.updates[self._poff]
                self._poff += 1
                self._took_private_locked(1, snapshot)
                return msg
            self._private.popleft()
            self._poff = 0
            if entry.progress_ts is not None and deliver_progress:
                self._took_private_locked(1, snapshot)
                return (int(entry.progress_ts), True, 0, None)
        return self._next_shared_locked()

    def _took_private_locked(self, n: int, snapshot: bool) -> None:
        self._priv_pending -= n
        if snapshot:
            self._snap_pending -= n

    def _next_shared_locked(self):
        ch = self.channel
        if ch is None:
            return None
        while True:
            entry = ch.entry_at(self._seq)
            if entry == "behind":
                self._shed_locked(
                    f"subscription {self.sub_id} on {self.object_name} shed: "
                    "cursor fell off the fan-out ring's retention window "
                    "(fanout_ring_ticks)"
                )
                return None
            if entry is None:
                return None
            if self._off < len(entry.updates):
                msg = entry.updates[self._off]
                self._off += 1
                return msg
            deliver_prog = entry.progress_ts is not None and self.progress
            self._seq += 1
            self._off = 0
            if deliver_prog:
                return (int(entry.progress_ts), True, 0, None)

    def _next_frame_locked(self, fmt: str):
        if self.state == "shed":
            return None
        while self._private:
            entry, deliver_progress, snapshot = self._private[0]
            msgs = list(entry.updates[self._poff:])
            if entry.progress_ts is not None and deliver_progress:
                msgs.append((int(entry.progress_ts), True, 0, None))
            self._private.popleft()
            self._poff = 0
            self._took_private_locked(len(msgs), snapshot)
            if not msgs:
                continue
            # per-subscriber encode (each snapshot is at its own as_of);
            # counted so encoded-vs-delivered stays honest
            data = ENCODERS[fmt](msgs, self.columns)
            _ENCODED.inc(1, format=fmt)
            return Frame(data, len(msgs))
        ch = self.channel
        if ch is None:
            return None
        while True:
            entry = ch.entry_at(self._seq)
            if entry == "behind":
                self._shed_locked(
                    f"subscription {self.sub_id} on {self.object_name} shed: "
                    "cursor fell off the fan-out ring's retention window "
                    "(fanout_ring_ticks)"
                )
                return None
            if entry is None:
                return None
            deliver_prog = entry.progress_ts is not None and self.progress
            n = len(entry.updates) - self._off + (1 if deliver_prog else 0)
            if n == 0:
                self._seq += 1
                self._off = 0
                continue
            if self._off == 0:
                # the hot path: the shared encode-once cache
                parts = []
                if entry.updates:
                    parts.append(ch.encoded(entry, fmt, "data"))
                if deliver_prog:
                    parts.append(ch.encoded(entry, fmt, "progress"))
                data = b"".join(parts)
            else:
                # mid-entry resumption after mixed pop()/pop_frame() use:
                # re-slice without touching the shared cache
                msgs = list(entry.updates[self._off:])
                if deliver_prog:
                    msgs.append((int(entry.progress_ts), True, 0, None))
                data = ENCODERS[fmt](msgs, self.columns)
            self._seq += 1
            self._off = 0
            return Frame(data, n)

    def _capture_shared_locked(self) -> None:
        """Move undelivered shared entries into the private deque (entry
        references, not payload copies) so a closed-but-draining stream
        survives ring trims that no longer count this cursor."""
        ch = self.channel
        if ch is None:
            return
        seq, off = self._seq, self._off
        while True:
            entry = ch.entry_at(seq)
            if entry is None or entry == "behind":
                break
            if off:
                entry = FrameEntry(
                    -1, entry.ts, entry.updates[off:], entry.progress_ts,
                    0, 0, columns=self.columns,
                )
            n = len(entry.updates) + (
                1 if (entry.progress_ts is not None and self.progress) else 0
            )
            if n:
                self._private.append((entry, self.progress, False))
                self._priv_pending += n
            seq, off = seq + 1, 0
        self._seq, self._off = seq, 0

    def _overflow_msg_locked(self) -> str:
        if self._shed_reason is not None:
            return self._shed_reason
        return (
            f"subscription {self.sub_id} on {self.object_name} shed: client "
            f"fell more than subscribe_queue_depth ({self.max_depth}) "
            "updates behind"
        )
