"""On-device shard exchange: hash-routed all_to_all over the device mesh.

The TPU re-design of timely's key-sharded exchange pacts and zero-copy TCP
mesh (reference: src/timely-util/src/pact.rs,
src/cluster/src/communication.rs:100): instead of per-worker sockets or the
host-staged pickled frames of `parallel/netexchange.py`, every tick's
shuffle is ONE `lax.all_to_all` over the mesh axis riding ICI. This module
is the ONLY home for device collectives in the tree — the
collective-coherence mzlint pass enforces that.

Routing is static-shape: each device packs its rows into `n_dest` buckets of
fixed capacity (destination = the shared `parallel/routing.route_mod` rule,
rank-within-destination computed by one sort + segmented arange:
`route_dest` and `bucket_rank` below), sends bucket i to device i, and
flattens what it receives. Overflow (more rows for one destination than
bucket capacity) is detected and reported as a flag the host reacts to by
re-running the tick with bigger buckets — the same pad-sentinel bucketing
discipline used everywhere else in the engine (`repr/batch.py`).

`mesh_jit` is the one entry point that stamps a tick function onto a mesh:
jit ∘ shard_map, with program/mesh metrics so a deployment can tell how many
device-collective programs it built and how wide the mesh under them is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...obs import metrics as obs_metrics
from ...ops.search import sort_perm
from ...repr.batch import PAD_TIME, UpdateBatch
from ...repr.hashing import PAD_HASH
from ..mesh import WORKERS
from ..routing import route_mod

try:
    _shard_map = jax.shard_map
except AttributeError:  # pragma: no cover - older jax spelling
    from jax.experimental.shard_map import shard_map as _shard_map

_PROGRAMS = obs_metrics.REGISTRY.counter(
    "mzt_device_exchange_programs_total",
    "device-collective tick programs stamped onto a mesh via mesh_jit "
    "(one bump per shard_map build, not per tick)",
    ("axis",),
)
_MESH_DEVICES = obs_metrics.REGISTRY.gauge(
    "mzt_device_exchange_mesh_devices",
    "devices on the mesh axis under the most recently built "
    "device-collective tick program",
    ("axis",),
)
_RETRIES = obs_metrics.REGISTRY.counter(
    "mzt_device_exchange_retries_total",
    "whole-tick re-runs after a routing-bucket overflow on a device mesh "
    "(the lossless capacity-doubling retry ladder, doc/DEVICE_MESH.md)",
)


def note_overflow_retry() -> None:
    """Record one overflow→regrow→re-run trip of the retry ladder."""
    _RETRIES.inc()


def route_dest(hashes: jnp.ndarray, n_dest: int) -> jnp.ndarray:
    """u32 hash → i32 destination shard: the rule the host mesh partitioner
    uses (`parallel/routing.route_mod`), so device and host routing agree."""
    return route_mod(hashes, n_dest).astype(jnp.int32)


def bucket_rank(key_s: jnp.ndarray) -> jnp.ndarray:
    """Rank of each row within its equal-key run of a sorted vector (the
    bucket slot it scatters to): ``idx - cummax(run_start ? idx : -1)``."""
    n = int(key_s.shape[0])
    idx = jnp.arange(n, dtype=jnp.int32)
    run_start = jnp.concatenate(
        [jnp.ones((1,), dtype=jnp.bool_), key_s[1:] != key_s[:-1]]
    )
    first_idx = jax.lax.cummax(jnp.where(run_start, idx, -1))
    return idx - first_idx


def route_to_buckets(batch: UpdateBatch, n_dest: int, bucket_cap: int):
    """Pack rows into [n_dest, bucket_cap] buckets by hash % n_dest.

    Returns (buckets pytree of [n_dest, bucket_cap] arrays, overflow flag).
    Dead rows (padding / diff 0) are not routed.
    """
    live = batch.live
    dest = route_dest(batch.hashes, n_dest)
    key = jnp.where(live, dest, n_dest)  # dead rows to a discard bucket
    order = sort_perm((key,))  # stable, i32 iota — no 64-bit sort operand
    key_s = key[order]
    # rank within each destination run
    rank = bucket_rank(key_s)
    overflow = jnp.any((key_s < n_dest) & (rank >= bucket_cap))
    ok = (key_s < n_dest) & (rank < bucket_cap)
    # non-routed rows scatter OUT OF BOUNDS so mode="drop" discards them —
    # aiming them at [0,0] would clobber whatever real row lives there
    d_idx = jnp.where(ok, key_s, n_dest)
    s_idx = jnp.where(ok, rank, bucket_cap)

    def scatter(col, fill):
        out = jnp.full((n_dest, bucket_cap), fill, dtype=col.dtype)
        return out.at[d_idx, s_idx].set(col[order], mode="drop")

    buckets = UpdateBatch(
        hashes=scatter(batch.hashes, PAD_HASH),
        keys=tuple(scatter(k, 0) for k in batch.keys),
        vals=tuple(scatter(v, 0) for v in batch.vals),
        times=scatter(batch.times, PAD_TIME),
        diffs=scatter(batch.diffs, 0),
    )
    return buckets, overflow


def exchange(batch: UpdateBatch, axis_name: str, n_dest: int, bucket_cap: int):
    """All-to-all shuffle by key hash (call under shard_map over `axis_name`).

    Every row lands on the device owning `hash % n_dest`. Returns
    (received batch of capacity n_dest*bucket_cap, overflow flag for THIS
    device's send side — psum it for a global flag).
    """
    buckets, overflow = route_to_buckets(batch, n_dest, bucket_cap)

    def a2a(x):
        return jax.lax.all_to_all(x, axis_name, 0, 0)

    recv = jax.tree_util.tree_map(a2a, buckets)
    flat = jax.tree_util.tree_map(lambda x: x.reshape((-1,) + x.shape[2:]), recv)
    return flat, overflow


def mesh_jit(fn, mesh, *, in_specs, out_specs, axis_name: str = WORKERS):
    """jit ∘ shard_map: the one place a tick function meets a device mesh.

    Every device-collective tick program in the engine is built here so the
    `mzt_device_exchange_*` metrics see them all and the lint surface stays
    one call wide.
    """
    axis = str(axis_name)
    _PROGRAMS.inc(axis=axis)
    _MESH_DEVICES.set(int(mesh.shape[axis]), axis=axis)
    return jax.jit(
        _shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    )
