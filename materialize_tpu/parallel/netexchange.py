"""Host-staged exchange routing: the network half of the exchange pacts.

`parallel/devicemesh/exchange.py` shuffles rows between *devices* inside one process
with a single `all_to_all` riding ICI. This module is the same pact at the
*process* boundary (the reference's zero-copy TCP worker mesh,
`src/cluster/src/communication.rs:100`): update batches are staged to host,
hash-partitioned by key columns with the engine's canonical row hash, and the
per-destination column dicts ride the framed CTP transport between shard
processes (`cluster/mesh.py`). The on-device collective counterpart landed
in `parallel/devicemesh/` (exchange_backend=device): inside one process the
shuffle is a single `lax.all_to_all`; this host plane remains the cross-host
seam, and the two compose (doc/DEVICE_MESH.md decision table).

Routing invariant: a row's destination worker depends only on the VALUES of
its routing columns (`routing.route_mod` of the canonical u32 row hash — the
same rule the device exchange and every arrangement uses), never on batch
boundaries or arrival order, so an insert and its later retraction always
land on the same worker and sharded results are deterministic.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..repr.batch import UpdateBatch
from ..repr.hashing import hash_columns_np
from .routing import route_mod


def batch_to_cols(batch: Optional[UpdateBatch]) -> Optional[dict]:
    """Trimmed host columns of a batch's live rows: {"c<i>", "times", "diffs"}.

    Returns None when there is nothing live — the wire format for "no data"
    (the punctuation-only frame still flows; see WorkerMesh.exchange).
    """
    if batch is None:
        return None
    h = batch.to_host()
    if len(h["times"]) == 0:
        return None
    cols = {f"c{i}": np.asarray(c) for i, c in enumerate(h["vals"])}
    cols["times"] = np.asarray(h["times"])
    cols["diffs"] = np.asarray(h["diffs"])
    return cols


def _val_cols(cols: dict) -> list[np.ndarray]:
    n = len([k for k in cols if k.startswith("c")])
    return [cols[f"c{i}"] for i in range(n)]


def route_dests(cols: dict, key_cols, n_workers: int) -> np.ndarray:
    """Destination worker per row.

    `key_cols`: tuple of column indices to route by; `None` means the whole
    row (source striping, threshold); `()` means keyless — a global group
    that must co-locate, so everything routes to worker 0.
    """
    nrows = len(cols["times"])
    if n_workers == 1 or key_cols == ():
        return np.zeros(nrows, dtype=np.int64)
    vals = _val_cols(cols)
    picked = vals if key_cols is None else [vals[i] for i in key_cols]
    if not picked:
        return np.zeros(nrows, dtype=np.int64)
    hashes = hash_columns_np(tuple(picked))
    # the ONE routing rule shared with the device plane (routing.route_mod)
    return route_mod(hashes, n_workers).astype(np.int64)


def partition_cols(cols: Optional[dict], key_cols, n_workers: int) -> list:
    """Split a host column dict into `n_workers` parts by routing hash."""
    if cols is None:
        return [None] * n_workers
    dests = route_dests(cols, key_cols, n_workers)
    parts: list = []
    for w in range(n_workers):
        mask = dests == w
        if not mask.any():
            parts.append(None)
        else:
            parts.append({k: v[mask] for k, v in cols.items()})
    return parts


def partition_batch(batch: Optional[UpdateBatch], key_cols, n_workers: int) -> list:
    return partition_cols(batch_to_cols(batch), key_cols, n_workers)


def merge_parts(parts: list) -> Optional[UpdateBatch]:
    """Concatenate received column-dict parts into one UpdateBatch."""
    live = [p for p in parts if p is not None and len(p["times"])]
    if not live:
        return None
    ncols = max(len(_val_cols(p)) for p in live)
    vals = tuple(
        np.concatenate([p[f"c{i}"] for p in live]) for i in range(ncols)
    )
    times = np.concatenate([p["times"] for p in live])
    diffs = np.concatenate([p["diffs"] for p in live])
    return UpdateBatch.build((), vals, times, diffs)
