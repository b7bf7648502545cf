"""The ONE shard-routing rule shared by the host and device exchange planes.

A row's destination shard is ``u32_key_hash % n_dest``, computed in u32 —
never widened, never re-hashed. `netexchange.route_dests` (host-staged
cross-process partitioning) and the device plane's `route_dest`
(`parallel/devicemesh/exchange.py`) both call :func:`route_mod`, so device
and host partitioning are provably identical: an insert routed by the host
mesh and its retraction routed by an on-device `all_to_all` land on the same
owner (the bit-equal-routing
invariant the mixed-mesh differentials rely on; motivated by the pure-
hash-function routing discipline of multiway hash joins on reconfigurable
hardware, PAPERS.md).
"""

from __future__ import annotations

import numpy as np


def route_mod(hashes, n_dest: int):
    """Destination shard per row: u32 hash mod ``n_dest``, computed in u32.

    Polymorphic over numpy and jax arrays (the modulus is an np.uint32
    scalar, which both promote without widening); callers cast the u32
    result to their index dtype (host: i64, device: i32) — the VALUES are
    identical because every destination fits either.
    """
    return hashes % np.uint32(n_dest)
