"""UpdateBatch — the universal device currency of the engine.

A batch is a fixed-capacity structure-of-arrays of update triples
``(key_cols, val_cols, time, diff)`` plus a precomputed u32 key hash, the TPU
re-design of the reference's update-triple collections
(doc/developer/change-data-capture.md:5-13) and of differential's `Batch`.

**Padding discipline.** Capacities are static for XLA; unused rows are padding
with ``hash == PAD_HASH`` (sorts last), ``diff == 0`` and ``time == PAD_TIME``.
Because every IVM operator is linear in ``diff``, diff==0 rows annihilate:
padding flows through joins/reduces/consolidation without masks. Capacities
are bucketed to powers of two so XLA recompiles O(log n) times, not O(n).

**32-bit device times.** Logical time is u64 on the host (frontiers,
antichains, `repr/timestamp.py` — the reference's `mz_repr::Timestamp`), but
the DEVICE view of time is u32: the TPU VPU is a 32-bit machine, and XLA
splits every u64 op into u32 pairs (X64SplitLow custom-calls, r2 profile), so
u64 time columns doubled the cost of every sort tiebreak, every
`max(t_l, t_r)` join rule, and the time column's HBM footprint. Times cross
the host↔device boundary through `to_device_time`/`device_time_scalar`, which
clamp real times into [0, MAX_DEVICE_TIME] — strictly below the u32 PAD_TIME
sentinel, so a real max-u32 time can never impersonate padding (the truncated
u64 all-ones sentinel WOULD equal 0xFFFFFFFF; the clamp is what keeps
"padding sorts last" and "pad rows annihilate" true under 32-bit views).
Engine times are tick counters, so the 2^32-2 ceiling is not a practical
bound; host-side logical times beyond it saturate rather than wrap.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.dtypes import canonicalize_dtype

from ..obs import metrics as obs_metrics
from .hashing import PAD_HASH, hash_columns, hash_columns_np, host_column

_BUILDS = obs_metrics.REGISTRY.counter(
    "mzt_batch_build_total",
    "UpdateBatch.build calls by where the columns lived: host (built in NumPy "
    "at capacity, one transfer, no XLA program) or device",
    labels=("path",),
)

# ---------------------------------------------------------------------------
# 64-bit boundary allowlist.
#
# Hot-path modules (ops/, arrangement/, parallel/exchange*) must not name
# 64-bit dtypes directly — scripts/lint_32bit.py enforces it — so every
# deliberate 64-bit device column is one of these aliases, decided HERE at
# the representation boundary:
#   TIME_DTYPE  u32 device time view (host logical time stays u64)
#   DIFF_DTYPE  i64 multiplicities, the reference's `Diff`
#               (src/repr/src/diff.rs:11); never a sort operand
#   I64_DTYPE   i64 SQL bigint data / error codes / aggregate accumulators
#               (value range is the point; also never a sort operand)
TIME_DTYPE = jnp.uint32
DIFF_DTYPE = jnp.int64
I64_DTYPE = jnp.int64

PAD_TIME = np.uint32(0xFFFFFFFF)
# Largest representable real (non-padding) device time; boundary conversions
# clamp here so no live row can collide with the PAD_TIME sentinel.
MAX_DEVICE_TIME = int(PAD_TIME) - 1
_PAD_TIME_U64 = np.uint64(0xFFFFFFFFFFFFFFFF)
MIN_CAP = 8


def device_time_scalar(t) -> np.uint32:
    """Host boundary: one logical (u64-domain) time → its u32 device view.

    Saturates at MAX_DEVICE_TIME (PAD_TIME is reserved for padding). Use for
    tick/since/as_of/until scalars handed to device kernels.
    """
    return np.uint32(min(max(int(t), 0), MAX_DEVICE_TIME))


def to_device_time(times) -> jnp.ndarray:
    """Array boundary: logical times (u64/i64/int) → u32 device views.

    The u64 all-ones padding sentinel maps to PAD_TIME; every other value
    saturates into [0, MAX_DEVICE_TIME]. u32 inputs pass through untouched
    (they are already device views).
    """
    t = jnp.asarray(times)
    if t.dtype == jnp.uint32:
        return t
    t32 = jnp.clip(t, 0, MAX_DEVICE_TIME).astype(TIME_DTYPE)
    if t.dtype == jnp.uint64:
        t32 = jnp.where(t == _PAD_TIME_U64, PAD_TIME, t32)
    return t32


def _host_time_view(times: np.ndarray) -> np.ndarray:
    """`to_device_time` in NumPy, for times that live on the host."""
    if times.dtype == np.uint32:
        return times
    if times.dtype.kind in "biu" and times.dtype.itemsize < 8:
        times = times.astype(np.int64)  # the clip's upper bound needs the room
    t32 = np.clip(times, 0, MAX_DEVICE_TIME).astype(np.uint32)
    if times.dtype == np.uint64:
        t32 = np.where(times == _PAD_TIME_U64, PAD_TIME, t32)
    return t32


def bucket_cap(n: int, minimum: int = MIN_CAP) -> int:
    """Round `n` up to the next power of two (at least `minimum`)."""
    c = minimum
    while c < n:
        c <<= 1
    return c


@jax.tree_util.register_pytree_node_class
@dataclass
class UpdateBatch:
    hashes: jnp.ndarray  # u32 [cap] — hash of key columns (PAD_HASH = padding)
    keys: tuple  # tuple of [cap] arrays (possibly empty tuple)
    vals: tuple  # tuple of [cap] arrays
    times: jnp.ndarray  # u32 [cap] — device time view (PAD_TIME = padding)
    diffs: jnp.ndarray  # i64 [cap]

    # -- pytree plumbing ---------------------------------------------------
    def tree_flatten(self):
        return (self.hashes, self.keys, self.vals, self.times, self.diffs), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # -- construction ------------------------------------------------------
    @staticmethod
    def empty(cap: int, key_dtypes=(), val_dtypes=()) -> "UpdateBatch":
        return UpdateBatch(
            hashes=jnp.full((cap,), PAD_HASH, dtype=jnp.uint32),
            keys=tuple(jnp.zeros((cap,), dtype=dt) for dt in key_dtypes),
            vals=tuple(jnp.zeros((cap,), dtype=dt) for dt in val_dtypes),
            times=jnp.full((cap,), PAD_TIME, dtype=TIME_DTYPE),
            diffs=jnp.zeros((cap,), dtype=DIFF_DTYPE),
        )

    @staticmethod
    def build(key_cols, val_cols, times, diffs, cap: int | None = None) -> "UpdateBatch":
        """Build a padded device batch from host (or device) columns.

        Host columns (NumPy arrays, lists: every ingest path) are assembled in
        NumPy at the batch's capacity and transferred once, so no XLA program
        ever holds the row count in its shape. Columns that already live on
        the device are cast, hashed and padded there. Both give the same
        batch bit for bit.
        """
        key_cols, val_cols = tuple(key_cols), tuple(val_cols)
        if any(isinstance(c, jax.Array) for c in (*key_cols, *val_cols, times, diffs)):
            _BUILDS.inc(path="device")
            return UpdateBatch._build_on_device(key_cols, val_cols, times, diffs, cap)
        _BUILDS.inc(path="host")
        key_cols = tuple(host_column(c) for c in key_cols)
        val_cols = tuple(host_column(c) for c in val_cols)
        times = _host_time_view(host_column(times))
        diffs = np.asarray(diffs, dtype=canonicalize_dtype(DIFF_DTYPE))
        n = int(times.shape[0])
        if cap is None:
            cap = bucket_cap(n)
        if key_cols:
            hashes = hash_columns_np(key_cols)
        else:
            hashes = np.zeros((n,), dtype=np.uint32)

        def padded(a, fill):
            # always a fresh buffer: a zero-copy device_put must not alias
            # an array the caller may write to again
            if a.shape != (n,):
                raise ValueError(f"column of shape {a.shape} in a batch of {n} rows")
            out = np.empty((cap,), dtype=a.dtype)
            out[:n] = a[:cap]
            out[n:] = fill
            return out

        return jax.device_put(
            UpdateBatch(
                padded(hashes, PAD_HASH),
                tuple(padded(k, 0) for k in key_cols),
                tuple(padded(v, 0) for v in val_cols),
                padded(times, PAD_TIME),
                padded(diffs, 0),
            )
        )

    @staticmethod
    def _build_on_device(key_cols, val_cols, times, diffs, cap) -> "UpdateBatch":
        key_cols = tuple(jnp.asarray(c) for c in key_cols)
        val_cols = tuple(jnp.asarray(c) for c in val_cols)
        times = to_device_time(times)
        diffs = jnp.asarray(diffs, dtype=DIFF_DTYPE)
        n = int(times.shape[0])
        if cap is None:
            cap = bucket_cap(n)
        if key_cols:
            hashes = hash_columns(key_cols)
        else:
            hashes = jnp.zeros((n,), dtype=jnp.uint32)
        b = UpdateBatch(hashes, key_cols, val_cols, times, diffs)
        return b.with_capacity(cap)

    # -- shape management --------------------------------------------------
    @property
    def cap(self) -> int:
        return int(self.times.shape[0])

    def with_capacity(self, cap: int) -> "UpdateBatch":
        cur = self.cap
        if cap == cur:
            return self
        if cap > cur:
            pad = cap - cur

            def ext(a, fill):
                return jnp.concatenate([a, jnp.full((pad,), fill, dtype=a.dtype)])

            return UpdateBatch(
                ext(self.hashes, PAD_HASH),
                tuple(ext(k, 0) for k in self.keys),
                tuple(ext(v, 0) for v in self.vals),
                ext(self.times, PAD_TIME),
                ext(self.diffs, 0),
            )
        # Shrink: only sound if rows beyond `cap` are padding; callers check.
        return UpdateBatch(
            self.hashes[:cap],
            tuple(k[:cap] for k in self.keys),
            tuple(v[:cap] for v in self.vals),
            self.times[:cap],
            self.diffs[:cap],
        )

    def permute(self, perm: jnp.ndarray) -> "UpdateBatch":
        return UpdateBatch(
            self.hashes[perm],
            tuple(k[perm] for k in self.keys),
            tuple(v[perm] for v in self.vals),
            self.times[perm],
            self.diffs[perm],
        )

    @staticmethod
    def concat(a: "UpdateBatch", b: "UpdateBatch") -> "UpdateBatch":
        return UpdateBatch(
            jnp.concatenate([a.hashes, b.hashes]),
            tuple(jnp.concatenate([x, y]) for x, y in zip(a.keys, b.keys)),
            tuple(jnp.concatenate([x, y]) for x, y in zip(a.vals, b.vals)),
            jnp.concatenate([a.times, b.times]),
            jnp.concatenate([a.diffs, b.diffs]),
        )

    # -- inspection --------------------------------------------------------
    @property
    def live(self) -> jnp.ndarray:
        """Mask of rows that carry information (non-padding, non-zero diff)."""
        return (self.hashes != PAD_HASH) & (self.diffs != 0)

    def count(self) -> jnp.ndarray:
        return jnp.sum(self.live.astype(jnp.int32))

    def to_host(self) -> dict:
        """Trimmed host copy: only live rows, in canonical order.

        A row's data is its `vals` columns; `keys` are an arrangement artifact
        (copies of key columns) and are not part of the row.
        """
        d = jax.device_get(
            (self.hashes, self.vals, self.times, self.diffs, self.live)
        )
        hashes, vals, times, diffs, live = d
        idx = np.nonzero(np.asarray(live))[0]
        rows = {
            "hashes": np.asarray(hashes)[idx],
            "vals": tuple(np.asarray(v)[idx] for v in vals),
            "times": np.asarray(times)[idx],
            "diffs": np.asarray(diffs)[idx],
        }
        order = np.lexsort(
            tuple(rows["vals"][::-1]) + (rows["times"], rows["hashes"])
        )
        return {
            k: (tuple(c[order] for c in v) if isinstance(v, tuple) else v[order])
            for k, v in rows.items()
        }

    def to_rows(self) -> list[tuple]:
        """Host rows as (val-cols tuple, time, diff) triples, canonically sorted.

        Float NaN (the float NULL sentinel) maps to None — host dict/compare
        semantics need NULL values that equal themselves."""
        from ..arrangement.spine import _host_value

        h = self.to_host()
        out = []
        for i in range(len(h["times"])):
            data = tuple(_host_value(c[i]) for c in h["vals"])
            out.append((data, int(h["times"][i]), int(h["diffs"][i])))
        return out
