"""Builtin introspection relations (the mz_internal analogue).

The reference surfaces engine internals as queryable relations built from
logging dataflows (src/compute/src/logging, src/catalog/src/builtin.rs —
mz_tables, mz_arrangement_sizes, mz_scheduling_elapsed, …). Here the same
names resolve to virtual collections whose contents are computed from the
live coordinator at peek time — same SQL surface, host-computed snapshot.
"""

from __future__ import annotations

import numpy as np

from ..repr.batch import UpdateBatch
from ..repr.types import ColType, RelationDesc


def _desc(*cols) -> RelationDesc:
    return RelationDesc.of(*cols)


INTROSPECTION_TABLES = {
    "mz_tables": _desc(("id", ColType.STRING), ("name", ColType.STRING)),
    "mz_views": _desc(("id", ColType.STRING), ("name", ColType.STRING)),
    "mz_materialized_views": _desc(("id", ColType.STRING), ("name", ColType.STRING)),
    "mz_sources": _desc(("id", ColType.STRING), ("name", ColType.STRING)),
    "mz_indexes": _desc(
        ("id", ColType.STRING), ("name", ColType.STRING), ("on_name", ColType.STRING)
    ),
    "mz_columns": _desc(
        ("object_name", ColType.STRING),
        ("name", ColType.STRING),
        ("position", ColType.INT64),
        ("type", ColType.STRING),
    ),
    "mz_dataflows": _desc(("id", ColType.STRING), ("name", ColType.STRING)),
    "mz_dataflow_operators": _desc(
        ("dataflow", ColType.STRING),
        ("operator_id", ColType.INT64),
        ("operator_type", ColType.STRING),
    ),
    "mz_scheduling_elapsed": _desc(
        ("dataflow", ColType.STRING),
        ("operator_id", ColType.INT64),
        ("operator_type", ColType.STRING),
        ("elapsed_ns", ColType.INT64),
        ("invocations", ColType.INT64),
        ("replica", ColType.STRING),  # "" = the coordinator's own dataflows
    ),
    "mz_dataflow_operator_rates": _desc(
        ("dataflow", ColType.STRING),
        ("operator_id", ColType.INT64),
        ("operator_type", ColType.STRING),
        ("rows_in", ColType.INT64),
        ("rows_out", ColType.INT64),
        ("retries", ColType.INT64),
        ("replica", ColType.STRING),
    ),
    "mz_hydration_statuses": _desc(
        ("dataflow", ColType.STRING),
        ("replica", ColType.STRING),
        ("hydrated", ColType.BOOL),
        ("frontier", ColType.INT64),
        ("as_of", ColType.INT64),
    ),
    "mz_source_statistics": _desc(
        ("id", ColType.STRING),
        ("name", ColType.STRING),
        ("offset_committed", ColType.INT64),
        ("bytes_received", ColType.INT64),
        ("records_received", ColType.INT64),
        ("lag_ms", ColType.INT64),
    ),
    "mz_trace_spans": _desc(
        ("id", ColType.INT64),
        ("parent", ColType.INT64),
        ("name", ColType.STRING),
        ("duration_ns", ColType.INT64),
        ("trace_id", ColType.INT64),
        ("process", ColType.STRING),
    ),
    "mz_peek_durations": _desc(
        ("bucket_ns_le", ColType.INT64),
        ("count", ColType.INT64),
    ),
    "mz_overload_counters": _desc(
        ("name", ColType.STRING),
        ("value", ColType.INT64),
    ),
    "mz_arrangement_sharing": _desc(
        ("trace_key", ColType.STRING),
        ("exporter", ColType.STRING),
        ("readers", ColType.INT64),
        ("since_hold", ColType.INT64),
        ("batches", ColType.INT64),
        ("capacity", ColType.INT64),
        ("records", ColType.INT64),
    ),
    "mz_subscriptions": _desc(
        ("id", ColType.STRING),
        ("object_name", ColType.STRING),
        ("state", ColType.STRING),
        ("queue_depth", ColType.INT64),
        ("delivered", ColType.INT64),
        ("shed_count", ColType.INT64),
        ("frontier", ColType.INT64),
        # appended (not inserted) so positional consumers of the original
        # seven columns keep working: the tenant charged by
        # max_subscriptions_per_user
        ("mz_user", ColType.STRING),
    ),
    "mz_sinks": _desc(
        ("id", ColType.STRING),
        ("name", ColType.STRING),
        ("from_name", ColType.STRING),
        ("path", ColType.STRING),
        ("format", ColType.STRING),
        ("frontier", ColType.INT64),
        ("emitted_updates", ColType.INT64),
        ("emitted_bytes", ColType.INT64),
    ),
    "mz_device_mesh": _desc(
        ("position", ColType.INT64),
        ("device", ColType.STRING),
        ("platform", ColType.STRING),
        ("axis", ColType.STRING),
        ("axis_size", ColType.INT64),
        ("in_mesh", ColType.BOOL),
        ("exchange_backend", ColType.STRING),
    ),
    "mz_arrangement_sizes": _desc(
        ("dataflow", ColType.STRING),
        ("operator_id", ColType.INT64),
        ("arrangement", ColType.STRING),
        ("batches", ColType.INT64),
        ("capacity", ColType.INT64),
        ("records", ColType.INT64),
        ("bytes", ColType.INT64),
        ("replica", ColType.STRING),
    ),
}


def _replica_operator_stats(coord) -> dict[tuple, list[int]]:
    """Operator accumulators shipped back from replica processes, merged per
    (replica, dataflow, operator, type) — several processes of one replica
    sum into one row, the partitioned-peek merge applied to logging."""
    merged: dict[tuple, list[int]] = {}
    for replica, rep in coord.replica_stats():
        for df_id, _obj, op_i, typ, el, inv, rin, rout, retries in rep.operators:
            cur = merged.setdefault((replica, df_id, op_i, typ), [0] * 5)
            cur[0] += int(el)
            cur[1] += int(inv)
            cur[2] += int(rin)
            cur[3] += int(rout)
            cur[4] += int(retries)
    return merged


def introspection_rows(coord, name: str) -> list[tuple]:
    """Current contents of one introspection relation (python values; strings
    stay python str — encoded by the virtual collection)."""
    cat = coord.catalog
    if name in ("mz_tables", "mz_views", "mz_materialized_views", "mz_sources"):
        kind = {
            "mz_tables": "table",
            "mz_views": "view",
            "mz_materialized_views": "materialized_view",
            "mz_sources": "source",
        }[name]
        return [
            (i.global_id, i.name) for i in cat.items.values() if i.kind == kind
        ]
    if name == "mz_indexes":
        return [
            (i.global_id, i.name, i.index_on or "")
            for i in cat.items.values()
            if i.kind == "index"
        ]
    if name == "mz_columns":
        out = []
        for it in cat.items.values():
            if it.desc is None:
                continue
            for pos, c in enumerate(it.desc.columns):
                out.append((it.name, c.name, pos, c.typ.value))
        return out
    if name == "mz_dataflows":
        gid2name = {i.global_id: i.name for i in cat.items.values()}
        return [(gid, gid2name.get(gid, gid)) for gid, _df, _src in coord.dataflows]
    if name == "mz_dataflow_operators":
        out = []
        for gid, df, _src in coord.dataflows:
            for obj, op_i, typ, _el, _inv in df.operator_info():
                out.append((gid, op_i, typ))
        return out
    if name == "mz_scheduling_elapsed":
        out = []
        for gid, df, _src in coord.dataflows:
            for obj, op_i, typ, el, inv in df.operator_info():
                out.append((gid, op_i, typ, el, inv, ""))
        for (replica, df_id, op_i, typ), v in _replica_operator_stats(coord).items():
            out.append((df_id, op_i, typ, v[0], v[1], replica))
        return out
    if name == "mz_dataflow_operator_rates":
        out = []
        for gid, df, _src in coord.dataflows:
            for obj, op_i, typ, rin, rout, retries in df.operator_rates():
                out.append((gid, op_i, typ, rin, rout, retries, ""))
        for (replica, df_id, op_i, typ), v in _replica_operator_stats(coord).items():
            out.append((df_id, op_i, typ, v[2], v[3], v[4], replica))
        return out
    if name == "mz_hydration_statuses":
        out = []
        for gid, df, _src in coord.dataflows:
            as_of = int(getattr(df.desc, "as_of", 0))
            fr = int(df.frontier)
            out.append((gid, "", fr > as_of, fr, as_of))
        for replica, rep in coord.replica_stats():
            for df_id, fr, as_of in rep.dataflows:
                out.append((df_id, replica, int(fr) > int(as_of), int(fr), int(as_of)))
        return out
    if name == "mz_source_statistics":
        import time as _t

        gid2name = {i.global_id: i.name for i in cat.items.values()}
        now = _t.time()
        out = []
        for gid, st in sorted(coord.source_stats.items()):
            lag_ms = int((now - st["updated"]) * 1000) if st["updated"] else 0
            out.append(
                (gid, gid2name.get(gid, gid), st["offset"], st["bytes"], st["records"], lag_ms)
            )
        return out
    if name == "mz_trace_spans":
        from ..utils.tracing import TRACER

        return [
            (s.id, s.parent, s.name, s.duration_ns, s.trace_id, s.process)
            for s in TRACER.recent()
            if s.duration_ns >= 0
        ]
    if name == "mz_peek_durations":
        return sorted(getattr(coord, "peek_histogram", {}).items())
    if name == "mz_overload_counters":
        # cumulative shed/cancel/yield counters plus live queue-depth gauges:
        # degradation decisions are queryable, not just logged
        counts = dict(coord.overload.snapshot())
        counts["statement_queue_depth"] = coord.admission.depth
        counts["peek_queue_depth"] = coord.peek_gate.depth
        return sorted(counts.items())
    if name == "mz_arrangement_sharing":
        # one row per shared trace (arrangement/trace_manager.py): who
        # exported it, how many readers hold it, and the current minimum
        # since hold — the sharing win (and the compaction laggard) is
        # queryable without a profiler
        return coord.trace_manager.sharing_rows()
    if name == "mz_subscriptions":
        # the egress plane's live state (queue depth, delivery progress,
        # shed accounting) — a stalled SUBSCRIBE client is diagnosable with
        # one SELECT instead of a heap dump
        return [
            (
                sid, sub.object_name, sub.state, sub.queue_depth(),
                sub.delivered, sub.shed_count, sub.frontier, sub.user,
            )
            for sid, sub in sorted(coord.subscriptions.items())
        ]
    if name == "mz_sinks":
        return [
            (
                gid, snk.name, snk.from_name, snk.path, snk.format,
                snk.frontier, snk.emitted_updates, snk.emitted_bytes,
            )
            for gid, snk in sorted(coord.sinks.items())
        ]
    if name == "mz_device_mesh":
        # one row per local device: mesh membership of the exchange plane
        # (parallel/devicemesh/). With no mesh-rendered dataflow (host mode)
        # the devices still list with in_mesh=false and axis_size=0, so the
        # table answers "what COULD a device mesh use here" anywhere.
        from ..parallel.devicemesh import device_mesh_rows

        mesh = getattr(coord, "mesh", None)
        for _gid, df, _src in coord.dataflows:
            m = getattr(df, "mesh", None)
            if m is not None:
                mesh = m
                break
        return device_mesh_rows(mesh, str(coord.configs.get("exchange_backend")))
    if name == "mz_arrangement_sizes":
        out = []
        for gid, df, _src in coord.dataflows:
            for obj, op_i, aname, nb, cap, rec, b in df.arrangement_info():
                out.append((gid, op_i, aname, nb, cap, rec, b, ""))
        merged: dict[tuple, list[int]] = {}
        for replica, rep in coord.replica_stats():
            for df_id, _obj, op_i, aname, nb, cap, rec, b in rep.arrangements:
                cur = merged.setdefault((replica, df_id, op_i, aname), [0] * 4)
                cur[0] += int(nb)
                cur[1] += int(cap)
                cur[2] += int(rec)
                cur[3] += int(b)
        for (replica, df_id, op_i, aname), v in merged.items():
            out.append((df_id, op_i, aname, v[0], v[1], v[2], v[3], replica))
        return out
    raise ValueError(f"unknown introspection relation {name}")


class IntrospectionCollection:
    """StorageCollection-shaped adapter over introspection_rows."""

    def __init__(self, coord, name: str, desc: RelationDesc):
        self.coord = coord
        self.name = name
        self.desc = desc
        self.dtypes = desc.dtypes

    def snapshot(self, as_of: int) -> UpdateBatch:
        rows = introspection_rows(self.coord, self.name)
        cols: list[list] = [[] for _ in self.desc.columns]
        for r in rows:
            for i, v in enumerate(r):
                if self.desc.columns[i].typ == ColType.STRING:
                    v = self.coord.catalog.dict.encode(str(v))
                cols[i].append(v)
        n = len(rows)
        arrays = tuple(
            np.array(c, dtype=self.desc.columns[i].dtype)
            for i, c in enumerate(cols)
        )
        return UpdateBatch.build(
            (), arrays, np.full(n, as_of, dtype=np.uint64), np.ones(n, dtype=np.int64)
        )
