"""Exchange routing kernels: destination map + rank-within-destination.

The device exchange plane (`parallel/devicemesh/exchange.py`) packs each
shard's rows into fixed-capacity per-destination buckets before one
``lax.all_to_all``. Its two integer primitives are registered here per the
kernel-registry contract (registry.py): an XLA lowering as the bit-identity
oracle plus a Pallas program, selected by the `kernel_backend` dyncfg.

- ``route_dest``  — u32 hash → i32 destination shard. The XLA oracle calls
  the SAME shared routing helper as the host mesh partitioner
  (`parallel/routing.route_mod`), which is what makes device and host
  routing provably identical.
- ``bucket_rank`` — given the destination keys in sorted order, the rank of
  each row within its destination run (the bucket slot it scatters to),
  computed as ``idx - cummax(run_start ? idx : -1)``.

Both are exact integer arithmetic, so the Pallas programs are bit-identical
to their oracles by construction (doc/KERNELS.md bit-identity rule).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import registry


# -- route_dest --------------------------------------------------------------


def _xla_route_dest(hashes: jnp.ndarray, n_dest: int) -> jnp.ndarray:
    """Reference oracle: the shared host/device routing rule, verbatim."""
    # imported at trace time, not module time: ops ↔ parallel would cycle
    from ...parallel.routing import route_mod

    return route_mod(hashes, n_dest).astype(jnp.int32)


def _pallas_route_dest(hashes: jnp.ndarray, n_dest: int) -> jnp.ndarray:
    n = int(hashes.shape[0])
    if n == 0 or hashes.ndim != 1:
        return _xla_route_dest(hashes, n_dest)
    h = hashes.reshape(1, n)
    nd = int(n_dest)  # static python scalar — pallas kernels can't capture arrays

    def kernel(h_ref, o_ref):
        o_ref[...] = (h_ref[...] % nd).astype(jnp.int32)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=registry.pallas_interpret(),
    )(h)
    return out.reshape((n,))


# -- bucket_rank -------------------------------------------------------------


def _xla_bucket_rank(key_s: jnp.ndarray) -> jnp.ndarray:
    """Reference oracle: rank within each equal-key run of a sorted vector."""
    n = int(key_s.shape[0])
    idx = jnp.arange(n, dtype=jnp.int32)
    run_start = jnp.concatenate(
        [jnp.ones((1,), dtype=jnp.bool_), key_s[1:] != key_s[:-1]]
    )
    first_idx = jax.lax.cummax(jnp.where(run_start, idx, -1))
    return idx - first_idx


def _pallas_bucket_rank(key_s: jnp.ndarray) -> jnp.ndarray:
    n = int(key_s.shape[0])
    if n == 0 or key_s.ndim != 1:
        return _xla_bucket_rank(key_s)
    k = key_s.reshape(1, n)

    def kernel(k_ref, o_ref):
        keys = k_ref[...]
        idx = jax.lax.broadcasted_iota(jnp.int32, (1, n), dimension=1)
        run_start = jnp.concatenate(
            [
                jnp.ones((1, 1), dtype=jnp.bool_),
                keys[:, 1:] != keys[:, :-1],
            ],
            axis=1,
        )
        # max-scan of (run_start ? idx : -1) in ceil(log2(n)) shift steps —
        # the same reduction-tree shape as the segsum kernel, with max as
        # the (associative, exact) combiner
        s = jnp.where(run_start, idx, jnp.int32(-1))
        d = 1
        while d < n:
            s_dn = jnp.concatenate(
                [jnp.full((1, d), -1, dtype=jnp.int32), s[:, :-d]], axis=1
            )
            s = jnp.maximum(s, s_dn)
            d <<= 1
        o_ref[...] = idx - s

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=registry.pallas_interpret(),
    )(k)
    return out.reshape((n,))


registry.register_kernel("route_dest", xla=_xla_route_dest, pallas=_pallas_route_dest)
registry.register_kernel("bucket_rank", xla=_xla_bucket_rank, pallas=_pallas_bucket_rank)
