"""Deterministic 32-bit row hashing on device.

Every update batch carries a u32 hash of its key columns; arrangements sort by
it, exchanges shard by it, joins probe by it. Collisions are handled (kernels
re-check key equality on gather), so the hash only needs uniformity.
Plays the role of the reference's key-hash exchange pacts
(src/timely-util/src/pact.rs and differential's `Hashable`).

u32, not u64, on purpose: the TPU VPU is a 32-bit machine — XLA splits every
u64 op into u32 pairs (X64SplitLow custom-calls, r2 profile), so u64 hashes
double the cost of the three hottest kernels (sort keys, binary-search
probes, exchange routing) and double the hash column's HBM footprint.
Collisions rise (~n²/2³³ colliding pairs) but every kernel already verifies
true key equality on gather, consolidation confirms runs by full-row
compare, and the reduce lookup's bucket-scan overflow is detected and
surfaced as an error — so a collision costs capacity, never correctness.
Mixing still runs through splitmix64 (u64) per column for quality; only the
final fold is 32-bit. The u64 mixing here is elementwise and tiny next to
the sort/probe kernels — it is the sanctioned 64-bit island of the
representation layer (see the boundary allowlist in repr/batch.py), kept
EXACTLY as-is so hash values (and therefore arrangement layouts, exchange
routing, and canonical row order) are bit-identical across the 32-bit-native
tick pipeline change.

Ordering keys derived from these hashes are (hi, lo) u32 PAIRS end-to-end
(ops/consolidate.pack_sort_key, ops/reduce._accum_pack): sorts take them as
two native u32 operands and probes compare them with two-key branchless
binary search (ops/search.py) — no packed u64 ever materializes on device.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax.dtypes import canonicalize_dtype

# splitmix64 constants (public domain PRNG finalizer, Steele et al.)
_C1 = np.uint64(0x9E3779B97F4A7C15)
_C2 = np.uint64(0xBF58476D1CE4E5B9)
_C3 = np.uint64(0x94D049BB133111EB)

# Reserved sentinel: padding rows hash to PAD_HASH and sort to the end of
# every batch. Real hashes are clamped below it.
PAD_HASH = np.uint32(0xFFFFFFFF)
_F32_TINY = np.finfo(np.float32).tiny


def splitmix64(x: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.uint64)
    x = x + _C1
    x = (x ^ (x >> np.uint64(30))) * _C2
    x = (x ^ (x >> np.uint64(27))) * _C3
    return x ^ (x >> np.uint64(31))


def _col_to_u64(col: jnp.ndarray) -> jnp.ndarray:
    """Canonical u64 view of one column for hashing."""
    return value_view(col).astype(jnp.uint64)


def value_view(col: jnp.ndarray) -> jnp.ndarray:
    """Total-order, equality-exact integer view of a column.

    The single canonicalization every value-identity kernel shares (hashing,
    consolidate runs, join/reduce/topk key equality): floats become u32 bit
    patterns with -0.0 folded into 0.0 and ALL NaNs folded to one canonical
    pattern — NaN is the engine's float NULL sentinel, and NULL must equal
    NULL for grouping/consolidation (IEEE NaN != NaN would make float-NULL
    rows unmergeable and unretractable).
    """
    if col.dtype == jnp.bool_:
        return col.astype(jnp.int8)
    if jnp.issubdtype(col.dtype, jnp.floating):
        f = col.astype(jnp.float32)
        f = jnp.where(f == 0.0, jnp.float32(0.0), f)  # -0.0 == 0.0
        f = jnp.where(jnp.isnan(f), jnp.float32(np.nan), f)  # canonical NaN
        return jax_bitcast_u32(f)
    return col


def jax_bitcast_u32(f: jnp.ndarray) -> jnp.ndarray:
    import jax.lax as lax

    return lax.bitcast_convert_type(f, jnp.uint32)


def hash_columns(cols: tuple[jnp.ndarray, ...]) -> jnp.ndarray:
    """Combine key columns into one u32 hash per row, clamped below PAD_HASH."""
    if not cols:
        # Keyless (global) groups: constant hash 0 routes everything together.
        raise ValueError("hash_columns needs at least one column; use zeros for keyless")
    h = jnp.full(cols[0].shape, np.uint64(0x51ED270B_9B1F8C33), dtype=jnp.uint64)
    for i, col in enumerate(cols):
        salt = np.uint64(((i + 1) * int(_C1)) % (1 << 64))
        h = splitmix64(h ^ splitmix64(_col_to_u64(col) + salt))
    h32 = (h ^ (h >> np.uint64(32))).astype(jnp.uint32)  # fold to 32 bits
    return jnp.where(h32 == PAD_HASH, PAD_HASH - np.uint32(1), h32)


def mix_columns(cols: tuple[jnp.ndarray, ...]) -> jnp.ndarray:
    """A second, independent u32 hash of the same columns.

    Paired with `hash_columns` to form a 64-bit ordering key for accumulator
    tables (reduce.py): rows agreeing on BOTH hashes but differing in keys
    need a ~2^-64 coincidence, which the merge kernels detect and surface
    loudly rather than mis-merge. Different init constant and salt stream
    than hash_columns, same splitmix64 mixing.
    """
    if not cols:
        return jnp.zeros((), dtype=jnp.uint32)
    h = jnp.full(cols[0].shape, np.uint64(0xA076_1D64_78BD_642F), dtype=jnp.uint64)
    for i, col in enumerate(cols):
        salt = np.uint64(((i + 7) * int(_C3)) % (1 << 64))
        h = splitmix64(h ^ splitmix64(_col_to_u64(col) ^ salt))
    return (h ^ (h >> np.uint64(32))).astype(jnp.uint32)


def host_column(col) -> np.ndarray:
    """A host column (array, list) at the dtype `jnp.asarray` would give it."""
    a = np.asarray(col)
    return a.astype(canonicalize_dtype(a.dtype), copy=False)


def _col_to_u64_np(col: np.ndarray) -> np.ndarray:
    """`_col_to_u64` on the host: `value_view`'s canonicalisation, then u64."""
    if col.dtype == np.bool_:
        return col.astype(np.uint64)
    if np.issubdtype(col.dtype, np.floating):
        with np.errstate(over="ignore"):  # a float64 past float32's range is inf
            f = col.astype(np.float32)
        # -0.0 == 0.0; XLA (CPU and TPU) also reads a subnormal as zero there
        f = np.where(np.abs(f) < _F32_TINY, np.float32(0.0), f)
        f = np.where(np.isnan(f), np.float32(np.nan), f)  # canonical NaN
        return f.view(np.uint32).astype(np.uint64)
    return col.astype(np.uint64)  # signed ints sign-extend, as XLA's convert


def _splitmix64_np(x: np.ndarray) -> np.ndarray:
    x = x + _C1  # u64 array arithmetic wraps, as on the device
    x = (x ^ (x >> np.uint64(30))) * _C2
    x = (x ^ (x >> np.uint64(27))) * _C3
    return x ^ (x >> np.uint64(31))


def hash_columns_np(cols) -> np.ndarray:
    """NumPy mirror of `hash_columns` (host-side oracle + batch construction).

    Bit-identical to the device hash and never touches the device: columns
    take the dtype `jnp.asarray` would give them, then the same splitmix64
    mixing in `np.uint64` array arithmetic.
    """
    if not cols:
        raise ValueError("hash_columns_np needs at least one column; use zeros for keyless")
    cols = [host_column(c) for c in cols]
    h = np.full(cols[0].shape, np.uint64(0x51ED270B_9B1F8C33), dtype=np.uint64)
    for i, col in enumerate(cols):
        salt = np.uint64(((i + 1) * int(_C1)) % (1 << 64))
        h = _splitmix64_np(h ^ _splitmix64_np(_col_to_u64_np(col) + salt))
    h32 = (h ^ (h >> np.uint64(32))).astype(np.uint32)  # fold to 32 bits
    return np.where(h32 == PAD_HASH, PAD_HASH - np.uint32(1), h32)
