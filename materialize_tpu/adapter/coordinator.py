"""Coordinator: the single-threaded command loop executing SQL.

The analogue of the reference's `Coordinator` (src/adapter/src/coord.rs:1989)
and its sequencer: DDL transacts against the catalog, INSERTs group-commit at
oracle write timestamps (coord/appends.rs), SELECTs choose between the index
fast path and an ephemeral one-shot dataflow (sequencer/inner/peek.rs:119),
materialized views install continuously-maintained dataflows whose outputs
feed storage collections (the persist-sink shape, sink/materialized_view.rs).
"""

from __future__ import annotations

import time as _time
from dataclasses import dataclass, field
from time import monotonic as _monotonic
from typing import Any, Optional

import numpy as np

from ..errors import QueryCanceled

from ..arrangement.spine import Arrangement
from ..dataflow import Dataflow
from ..dataflow import plan as lir
from ..expr import relation as mir
from ..obs import log as obs_log
from ..obs import metrics as obs_metrics
from ..obs.spans import TRACER
from ..ops.consolidate import advance_times, consolidate
from ..repr.batch import UpdateBatch
from ..repr.types import ColType, ColumnDesc, RelationDesc
from ..sql import ast
from ..sql.lower import Lowerer, lower_to_dataflow
from ..sql.parser import parse_statement, parse_statements
from ..sql.plan import PlanError, Planner, PlannedQuery, PType
from ..storage.generator import AuctionGenerator, CounterGenerator, TpchGenerator
from ..transform import optimize
from .catalog import Catalog, CatalogItem, coltype_of
from .timestamp_oracle import TimestampOracle

_log = obs_log.get_logger("coord")

# Per-dataflow write-tick duration (the coordinator's in-process dataflows;
# clusterd's come back merged in StatsReport) — a /metrics histogram family.
_TICK_NS = obs_metrics.REGISTRY.histogram(
    "mzt_dataflow_tick_duration_ns",
    "duration of one dataflow step at one write timestamp",
    labels=("dataflow",),
)


@dataclass
class ExecResult:
    kind: str  # rows | status
    rows: list = field(default_factory=list)
    columns: tuple = ()
    status: str = "ok"


def parse_replica_size(size: str) -> tuple[int, int]:
    """Parse a replica size into (processes, workers_per_process).

    The reference's cluster replica sizes name a process × worker split
    (`src/adapter/src/catalog.rs` cluster_replica_sizes, e.g. "2-4" = 2
    processes × 4 workers); here the spelling is "PxW": "2x4" is 2 clusterd
    shard processes hosting 4 workers each, and a bare "4" is the
    single-process 4-worker shape.
    """
    s = size.strip().lower()
    try:
        if "x" in s:
            p_str, w_str = s.split("x", 1)
            procs, workers = int(p_str), int(w_str)
        else:
            procs, workers = 1, int(s)
    except ValueError:
        raise ValueError(f"invalid replica size {size!r}: want 'PxW' or 'W'")
    if procs < 1 or workers < 1:
        raise ValueError(f"invalid replica size {size!r}: counts must be >= 1")
    return procs, workers


def _migrate_catalog_v1(doc: dict) -> dict:
    """v1 (unstamped) → v2: normalize item fields added over the format's
    life, so the post-migration doc satisfies the v2 schema exactly."""
    for d in doc.get("items", []):
        d.setdefault("append_only", False)
        d.setdefault("options", ())
        d.setdefault("generator", None)
    return doc


_CATALOG_MIGRATIONS = {1: _migrate_catalog_v1}


def _migrate_catalog_doc(doc: dict) -> dict:
    """Upgrade a durable catalog doc to the current format version.

    Older versions migrate step-by-step; a NEWER version refuses to boot
    with a clear error — misreading a future format would corrupt the
    catalog on the next persist (the reference's durable-catalog version
    gate, src/catalog/src/durable/upgrade.rs)."""
    from ..persist import CATALOG_VERSION

    version = doc.get("version", 1)
    if version > CATALOG_VERSION:
        raise RuntimeError(
            f"catalog format v{version} is newer than this build supports "
            f"(v{CATALOG_VERSION}): refusing to boot; upgrade the binary "
            "or point at a compatible data_dir"
        )
    while version < CATALOG_VERSION:
        doc = _CATALOG_MIGRATIONS[version](doc)
        version += 1
        doc["version"] = version
    return doc


def _batch_to_cols(batch: UpdateBatch) -> dict:
    """Host column dict ({'c0':…, 'times':…, 'diffs':…}) from a device
    batch — the persist wire layout (shard.py encode_columns)."""
    h = batch.to_host()
    cols = {f"c{i}": c for i, c in enumerate(h["vals"])}
    cols["times"] = h["times"]
    cols["diffs"] = h["diffs"]
    return cols


class StorageCollection:
    """Host-side durable collection of update batches (persist-lite).

    Mirrors a persist shard's role: the definite record of a table/source/
    materialized view, readable as a snapshot at any time ≤ upper.
    """

    def __init__(self, dtypes: tuple):
        self.dtypes = tuple(dtypes)
        self.arr = Arrangement(key_cols=())
        self.upper = 0

    def append(self, batch: UpdateBatch, tick: int) -> None:
        self.arr.insert(batch)
        self.upper = max(self.upper, tick + 1)

    def snapshot(self, as_of: int) -> UpdateBatch:
        """Consolidated contents as of `as_of` (times advanced to as_of)."""
        if not self.arr.batches:
            return UpdateBatch.empty(8, (), self.dtypes)
        merged = self.arr.merged()
        return consolidate(advance_times(merged, as_of))


class Coordinator:
    """Pass `data_dir` (or blob+consensus) for durability: the catalog and
    every collection live in persist shards and a restart rebuilds dataflows
    and rehydrates arrangements from snapshots — the reference's recovery
    model (SURVEY.md §5 checkpoint/resume: durable state is only shards +
    the durable catalog; everything else re-renders)."""

    def __init__(
        self, data_dir: str | None = None, blob=None, consensus=None,
        preflight: bool = False, mesh=None,
    ) -> None:
        # with `mesh`, fused dataflows run shard_map-sharded over its
        # `workers` axis (multi-worker SQL execution; parallel/devicemesh/exchange.py)
        self.mesh = mesh
        self.catalog = Catalog()
        self.oracle = TimestampOracle()
        self.storage: dict[str, StorageCollection] = {}
        self.generators: list = []  # (generator, {table -> gid})
        # per-source ingestion statistics (mz_source_statistics): resume
        # offset, cumulative bytes/records, last-update wall clock (lag)
        self.source_stats: dict[str, dict] = {}
        # installed continuous dataflows in dependency order: (mv_gid, Dataflow, src_gids)
        self.dataflows: list = []
        self.planner = Planner(self.catalog)
        from .dyncfg import default_configs
        from .overload import AdmissionGate, OverloadStats

        self.configs = default_configs()
        # overload protection: every shed/cancel/yield decision is counted
        # (mz_overload_counters); the gates bound the waiting line in front
        # of the single-threaded command loop (adapter/overload.py)
        self.overload = OverloadStats()
        self.admission = AdmissionGate(
            "statement", lambda: self.configs.get("coord_queue_depth"), self.overload
        )
        self.peek_gate = AdmissionGate(
            "peek", lambda: self.configs.get("peek_queue_depth"), self.overload
        )
        # pgwire cancellation registry: backend pid -> (secret key, session);
        # a CancelRequest must present the exact secret or it is a no-op
        self.cancel_keys: dict[int, tuple] = {}
        # cross-dataflow arrangement sharing (arrangement/trace_manager.py):
        # dataflows reading the same collection share one arrangement per
        # (collection, key) with reader-held compaction; the dyncfg
        # enable_arrangement_sharing force-disables for bisection
        from ..arrangement.trace_manager import TraceManager

        self.trace_manager = TraceManager()
        self.blob = blob
        self.consensus = consensus
        if data_dir is not None:
            from ..persist import FileBlob, FileConsensus

            self.blob = FileBlob(f"{data_dir}/blob")
            self.consensus = FileConsensus(f"{data_dir}/consensus")
        # crash-point injection (persist/crashpoints.py): when a CrashPlan is
        # installed — by a test, or via MZT_CRASH_SPEC in a subprocess — every
        # durable op goes through the seeded crash schedule
        from ..persist import crashpoints

        self.blob, self.consensus = crashpoints.wrap_if_installed(
            self.blob, self.consensus
        )
        self.shards: dict[str, object] = {}  # gid -> ShardMachine
        # name -> (controller, orchestrator, owned) — see create_compute_replica
        self._compute_replicas: dict[str, tuple] = {}
        # 0dt deployment state machine (deployment/state.rs:19-24 analogue):
        # init → catching-up (preflight, read-only) → leader; stale leaders
        # become "fenced" when a newer generation takes over.
        self.deploy_state = "init"
        self.epoch = 0
        # egress plane (materialize_tpu/egress): push SUBSCRIBE cursors over
        # the shared fan-out ring, and exactly-once file sinks, both fed by
        # _apply_writes' egress tick. One frame per (collection, tick) is
        # published into `fanout` and shared zero-copy by every subscriber.
        from ..egress import FanoutTree

        self.subscriptions: dict[str, Any] = {}
        self.sinks: dict[str, Any] = {}
        self.fanout = FanoutTree(
            retention=lambda: int(self.configs.get("fanout_ring_ticks"))
        )
        self._sub_seq = 0
        self._register_introspection()
        if self.durable:
            self._boot(read_only=preflight)
            if preflight:
                self.deploy_state = "catching-up"
            else:
                self._take_leadership()
        else:
            self.deploy_state = "leader"

    def _register_introspection(self) -> None:
        from .introspection import INTROSPECTION_TABLES, IntrospectionCollection

        if not bool(self.configs.get("enable_introspection")):
            return  # boot-time opt-out: no mz_* relations in the catalog
        for name, desc in INTROSPECTION_TABLES.items():
            item = CatalogItem(name, "introspection", desc=desc, global_id=f"si_{name}")
            self.catalog.items[name] = item
            self.storage[item.global_id] = IntrospectionCollection(self, name, desc)

    @property
    def durable(self) -> bool:
        return self.blob is not None and self.consensus is not None

    # -- public API ----------------------------------------------------------
    def new_session(self):
        from .dyncfg import SessionConfigs

        return SessionConfigs(self.configs)

    def execute(self, sql: str, session=None, params=None) -> ExecResult:
        stmt = parse_statement(sql)
        return self.execute_stmt(stmt, session, params=params)

    def execute_script(self, sql: str, session=None, params=None) -> list[ExecResult]:
        return [
            self.execute_stmt(s, session, params=params)
            for s in parse_statements(sql)
        ]

    def execute_stmt(self, stmt, session=None, params=None) -> ExecResult:
        from ..utils.tracing import TRACER

        self._session = session  # per-statement; coordinator is single-threaded
        self.planner.set_params(params)
        # NOTE: session.cancelled is deliberately NOT cleared here. A cancel
        # targets the in-flight QUERY MESSAGE, which may be a multi-statement
        # script — clearing per statement would drop a cancel at the next
        # statement boundary. The protocol layer (pgwire) clears the event
        # once per incoming query message instead.
        timeout_ms = int(self._cfg().get("statement_timeout"))
        # The timer starts at query RECEIPT when the protocol layer stamped
        # one (pg semantics): time spent waiting in the admission queue and
        # on the coordinator lock counts against the budget, so a statement
        # that queued past its deadline cancels at the entry checkpoint
        # instead of running arbitrarily late. Consumed once — later
        # statements of the same script start their own windows.
        t0 = _monotonic()
        if session is not None:
            arrival = getattr(session, "arrival", None)
            if arrival is not None:
                t0 = arrival
                session.arrival = None
        self._deadline = t0 + timeout_ms / 1000.0 if timeout_ms > 0 else None
        try:
            # a top-level statement mints a fresh TRACE (its context rides
            # CTP to clusterd and remote spans ship back — obs/spans.py); a
            # nested execute (EXPLAIN TIMELINE's inner run) records a child
            # span in the enclosing trace instead
            name = f"execute:{type(stmt).__name__}"
            cm = (
                TRACER.span(name)
                if TRACER.current_context() is not None
                else TRACER.trace(name)
            )
            with cm as s:
                self.last_trace_id = s.trace_id
                return self._execute_stmt_inner(stmt)
        except Exception as e:
            from ..errors import ResultSizeExceeded

            if isinstance(e, ResultSizeExceeded):
                self.overload.bump("result_size_rejections")
            raise
        finally:
            self._deadline = None
            self.planner.set_params(None)

    def check_cancellation(self) -> None:
        """Cooperative checkpoint (57014): raises QueryCanceled once the
        statement's deadline passed or its session was canceled. Installed as
        `Dataflow.cancel_check` on ephemeral peek dataflows and called at
        coordinator read-path boundaries; NEVER consulted past a durable
        commit point, so a timeout can't tear a write."""
        s = getattr(self, "_session", None)
        if (
            s is not None
            and getattr(s, "cancelled", None) is not None
            and s.cancelled.is_set()
        ):
            self.overload.bump("cancels_honored")
            raise QueryCanceled("canceling statement due to user request")
        dl = getattr(self, "_deadline", None)
        if dl is not None and _monotonic() >= dl:
            self.overload.bump("statement_timeouts")
            raise QueryCanceled("canceling statement due to statement timeout")

    def _cfg(self):
        """Effective configs: session overlay when a session is active."""
        return self._session if getattr(self, "_session", None) is not None else self.configs

    def _execute_stmt_inner(self, stmt) -> ExecResult:
        # entry checkpoint: a statement admitted after its deadline (it sat
        # in the admission queue too long) cancels BEFORE doing any work —
        # nothing durable has happened yet for any statement kind
        self.check_cancellation()
        if isinstance(stmt, ast.CreateTable):
            return self._create_table(stmt)
        if isinstance(stmt, ast.CreateSource):
            return self._create_source(stmt)
        if isinstance(stmt, ast.CreateFileSource):
            return self._create_file_source(stmt)
        if isinstance(stmt, ast.CreateView):
            return self._create_view(stmt)
        if isinstance(stmt, ast.CreateMaterializedView):
            return self._create_materialized_view(stmt)
        if isinstance(stmt, ast.CreateIndex):
            return self._create_index(stmt)
        if isinstance(stmt, ast.Insert):
            return self._insert(stmt)
        if isinstance(stmt, ast.Delete):
            return self._delete(stmt)
        if isinstance(stmt, ast.SelectStatement):
            return self._select(stmt.query)
        if isinstance(stmt, ast.Explain):
            return self._explain(stmt)
        if isinstance(stmt, ast.Show):
            return self._show(stmt)
        if isinstance(stmt, ast.DropObject):
            return self._drop(stmt)
        if isinstance(stmt, ast.Subscribe):
            return self._subscribe(stmt)
        if isinstance(stmt, ast.CreateSink):
            return self._create_sink(stmt)
        if isinstance(stmt, ast.SetVariable):
            target = (
                self.configs
                if stmt.system or getattr(self, "_session", None) is None
                else self._session
            )
            if stmt.name == "exchange_backend":
                from ..parallel.devicemesh import EXCHANGE_MODES

                if str(stmt.value) not in EXCHANGE_MODES:
                    raise PlanError(
                        f"invalid value for exchange_backend: {stmt.value!r} "
                        f"(expected one of {', '.join(EXCHANGE_MODES)})"
                    )
            try:
                target.set(stmt.name, stmt.value)
            except KeyError as e:
                raise PlanError(str(e))
            if stmt.name == "log_filter":
                from ..utils.tracing import TRACER

                TRACER.set_filter(self._cfg().get("log_filter"))
            elif stmt.name == "enable_operator_logging":
                # flip LIVE dataflows too — newly rendered ones read the
                # config at construction (_make_dataflow)
                on = bool(self._cfg().get("enable_operator_logging"))
                for _gid, df, _srcs in self.dataflows:
                    df.operator_logging = on
            elif stmt.name in ("enable_jax_profiler", "jax_profiler_dir"):
                from ..obs import profiler

                profiler.configure(
                    bool(self._cfg().get("enable_jax_profiler")),
                    str(self._cfg().get("jax_profiler_dir")),
                )
            return ExecResult("status", status="SET")
        if isinstance(stmt, ast.ResetVariable):
            if stmt.name not in self.configs.names():
                raise PlanError(
                    f"unknown configuration parameter: {stmt.name}"
                )
            target = (
                self._session
                if getattr(self, "_session", None) is not None
                else self.configs
            )
            target.reset(stmt.name)
            return ExecResult("status", status="RESET")
        if isinstance(stmt, ast.Update):
            return self._update(stmt)
        if isinstance(stmt, ast.Copy):
            return self._copy(stmt)
        raise PlanError(f"unsupported statement: {type(stmt).__name__}")

    def _copy(self, stmt: ast.Copy) -> ExecResult:
        """COPY … TO STDOUT (reference: pgwire COPY + copy_to sinks)."""
        if stmt.format not in ("csv", "text"):
            raise PlanError(f"unsupported COPY format {stmt.format}")
        res = self._select(stmt.query)
        import csv as _csv
        import io as _io

        buf = _io.StringIO()
        if stmt.format == "csv":
            w = _csv.writer(buf, lineterminator="\n")  # Postgres COPY uses \n
            for row in res.rows:
                w.writerow(row)
        else:
            for row in res.rows:
                buf.write("\t".join(str(v) for v in row) + "\n")
        out = ExecResult("copy", columns=res.columns, status=f"COPY {len(res.rows)}")
        out.copy_data = buf.getvalue()
        return out

    # -- egress: subscriptions + sinks ----------------------------------------
    def _subscribe(self, stmt: ast.Subscribe) -> ExecResult:
        """SUBSCRIBE: tap a collection's changelog (reference:
        src/compute/src/sink/subscribe.rs). Registers a push `Subscription`
        (egress/subscribe.py) fed at every commit tick; pgwire streams it as
        COPY-out rows and the HTTP server as NDJSON, while
        `poll_subscription` remains the pull shape."""
        from ..egress import Subscription
        from ..errors import TooManySubscriptions

        # per-tenant admission budget (on top of the PR 6 gates): one user
        # may not exhaust the fan-out ring's cursor table; retryable 53300
        user = getattr(getattr(self, "_session", None), "user", None) or "anonymous"
        per_user = int(self._cfg().get("max_subscriptions_per_user"))
        if per_user > 0:
            live = sum(1 for s in self.subscriptions.values() if s.user == user)
            if live >= per_user:
                self.overload.bump("subscriptions_rejected")
                raise TooManySubscriptions(
                    f"user {user!r} already holds {live} subscriptions "
                    f"(max_subscriptions_per_user = {per_user}); retry later"
                )

        pq = self.planner.plan_query(stmt.query)
        rel = optimize(pq.mir, self.configs)
        hidden = None
        if isinstance(rel, mir.MirGet) and (
            any(g == rel.id for g, _df, _s in self.dataflows)
            or rel.id in self.storage
        ):
            gid = rel.id
        else:
            # materialize the query under a hidden name, then tail it
            hidden = f"_sub_{self._sub_seq}"
            self.execute_stmt(ast.CreateMaterializedView(hidden, stmt.query))
            gid = self.catalog.get(hidden).global_id
        sub_id = f"sub{self._sub_seq}"
        self._sub_seq += 1
        obj_name = hidden or next(
            (it.name for it in self.catalog.items.values() if it.global_id == gid),
            gid,
        )
        columns = tuple(c.name for c in pq.desc.columns)
        sub = Subscription(
            sub_id, gid, obj_name, pq, columns,
            snapshot=stmt.snapshot, progress=stmt.progress,
            max_depth=int(self._cfg().get("subscribe_queue_depth")),
            hidden_mv=hidden,
            # the cursor attaches at the shared ring's head: ticks from now
            # on arrive as shared frames, the snapshot below as a private
            # preamble (it is at this subscriber's own as_of)
            channel=self.fanout.channel(gid, columns),
            user=user,
        )
        as_of = self.oracle.read_ts()
        updates = []
        if stmt.snapshot:
            updates = self._batch_updates(
                self.storage[gid].snapshot(as_of),
                lambda r: self._decode_row(r, pq),
            )
        sub.frontier = as_of + 1
        # pin the decode schema and seed the read hold on the CHANNEL: the
        # tick loop and the compaction driver iterate channels, never the
        # (possibly 10k-wide) subscriber population
        sub.channel.pq = pq
        if sub.channel.frontier <= as_of:
            sub.channel.frontier = as_of + 1
        if updates or stmt.progress:
            sub.publish(
                updates,
                progress_ts=(as_of + 1) if stmt.progress else None,
                snapshot=True,
            )
        self.subscriptions[sub_id] = sub
        out = ExecResult("subscribe", status=sub_id, columns=sub.columns)
        out.subscription = sub
        return out

    def poll_subscription(self, sub_id: str):
        """Drain queued updates: ([(row…, ts, diff)], frontier) — the HTTP
        long-poll shape; progress markers are push-stream only."""
        sub = self.subscriptions[sub_id]
        rows = [
            (row, ts, d)
            for ts, progressed, d, row in sub.drain()
            if not progressed
        ]
        rows.sort(key=lambda r: r[1])
        return rows, sub.frontier

    def teardown_subscription(self, sub_id: str, state: str = "cancelled") -> None:
        """Remove a subscription and release what it holds: its compaction
        read hold (it leaves the hold scan) and, for an ad-hoc query, the
        hidden _sub_N materialized view — whose drop releases the shared
        trace holds the render registered."""
        sub = self.subscriptions.pop(sub_id, None)
        if sub is None:
            return
        sub.close(state)
        if sub.hidden_mv is not None and sub.hidden_mv in self.catalog.items:
            self._drop(
                ast.DropObject("materialized view", sub.hidden_mv, if_exists=True)
            )

    def _batch_updates(self, batch, decode) -> list:
        """Consolidated, decoded `(ts, diff, row)` triples from a device
        batch; numpy scalars are normalized so rows are JSON-encodable."""
        if batch is None or not int(batch.count()):
            return []
        h = consolidate(batch).to_host()
        out = []
        for i in range(len(h["times"])):
            raw = tuple(col[i] for col in h["vals"])
            row = tuple(
                v.item() if hasattr(v, "item") else v for v in decode(raw)
            )
            out.append((int(h["times"][i]), int(h["diffs"][i]), row))
        return out

    def _decode_desc_row(self, row: tuple, desc: RelationDesc) -> tuple:
        """Decode an encoded host row against a RelationDesc — the egress
        decode path (sinks carry a catalog desc, not a planned-query scope)."""
        from ..expr.scalar import is_null_value

        out = []
        for v, c in zip(row, desc.columns):
            if is_null_value(v, c.typ):
                out.append(None)
            elif c.typ in (ColType.STRING, ColType.JSONB):
                out.append(self.catalog.dict.decode(int(v)))
            elif c.typ == ColType.NUMERIC and c.scale:
                out.append(v / (10**c.scale))
            elif c.typ == ColType.BOOL:
                out.append(bool(v))
            else:
                out.append(v)
        return tuple(out)

    def _create_sink(self, stmt: ast.CreateSink) -> ExecResult:
        """CREATE SINK … INTO FILE: catalog the sink, start its changelog at
        byte 0, and emit the source's existing history as the first frame —
        through the same exactly-once protocol as steady state, so a crash
        anywhere inside CREATE converges at the next boot's resume."""
        from ..egress import FileSink, progress_shard_id

        src = self.catalog.get(stmt.from_name)
        if src.kind not in ("table", "source", "materialized_view"):
            raise PlanError(
                f"CREATE SINK FROM {stmt.from_name}: need a table, source, "
                f"or materialized view, not a {src.kind}"
            )
        item = self.catalog.create(
            CatalogItem(
                stmt.name, "sink", desc=src.desc,
                options=(
                    ("from", stmt.from_name),
                    ("path", stmt.path),
                    ("format", stmt.format),
                ),
            )
        )
        sink = FileSink(
            item.global_id, stmt.name, stmt.from_name, src.global_id,
            stmt.path, stmt.format, src.desc,
        )
        with open(stmt.path, "wb"):
            pass  # the sink owns its changelog from byte 0
        self.sinks[item.global_id] = sink
        self._persist_catalog()
        if self.durable:
            # history so far = the source shard's contents; emitting it via
            # resume makes CREATE identical to the boot repair path
            sink.resume(
                self._shard(progress_shard_id(item.global_id)),
                lambda lo, hi, s=sink: self._sink_derive(s, lo, hi),
                epoch=self.epoch,
                order=str(self.configs.get("sink_commit_order")),
            )
        else:
            store = self.storage[src.global_id]
            updates = []
            if store.arr.batches:
                updates = self._batch_updates(
                    store.arr.merged(),
                    lambda r, s=sink: self._decode_desc_row(r, s.desc),
                )
            sink.emit(updates, store.upper)
        return ExecResult("status", status="CREATE SINK")

    def _register_sink(self, item: CatalogItem, resume: bool = True) -> None:
        """Rebuild a FileSink from its catalog options at boot. `resume`
        runs the exactly-once repair + catch-up (leaders-to-be only: a
        read-only generation loads the durable cursor without touching the
        changelog file)."""
        from ..egress import FileSink, progress_shard_id

        opts = dict(item.options)
        src = self.catalog.get(opts["from"])
        sink = FileSink(
            item.global_id, item.name, src.name, src.global_id,
            opts["path"], opts["format"], item.desc,
        )
        self.sinks[item.global_id] = sink
        m = self._shard(progress_shard_id(item.global_id))
        if resume:
            # epoch=None: pre-leadership, like _reconcile_mv_shard
            sink.resume(
                m,
                lambda lo, hi, s=sink: self._sink_derive(s, lo, hi),
                order=str(self.configs.get("sink_commit_order")),
            )
        else:
            row, _upper = sink.read_register(m)
            if row is not None:
                sink.offset, sink.frontier = row[1], row[3]

    def _sink_derive(self, sink, lo_ts: int, hi_ts):
        """Decoded source updates with lo_ts ≤ time < hi_ts from the durable
        shard (hi_ts None = everything committed), for sink frame
        (re-)derivation. Returns `(updates, upper)`."""
        m = self._shard(sink.from_gid)
        payloads, upper = m.listen_from(lo_ts)
        ncols = len(sink.desc.columns)
        updates = []
        for cols in payloads:
            for i in range(len(cols["times"])):
                t = int(cols["times"][i])
                if t < lo_ts or (hi_ts is not None and t >= hi_ts):
                    continue
                raw = tuple(cols[f"c{j}"][i] for j in range(ncols))
                updates.append(
                    (t, int(cols["diffs"][i]), self._decode_desc_row(raw, sink.desc))
                )
        return updates, (upper if hi_ts is None else hi_ts)

    def _egress_tick(self, env: dict, ts: int, persist: bool) -> None:
        """Feed the egress plane one commit tick: push each live
        subscription's decoded deltas (+ PROGRESS marker), then append each
        file sink's frame with its durable progress commit (egress/sink.py
        protocol). Runs after the tick's shard writes, so a crash here never
        leaves a sink ahead of its source shard."""
        from ..egress import progress_shard_id
        from ..persist import Fenced

        # each (collection, columns) channel decodes and publishes ONE frame
        # entry per tick, shared zero-copy by every cursor — fan-out work is
        # O(channels), not O(subscribers): per-cursor accounting is the
        # channel's O(1) floor check (Channel.shared_tick), and the read
        # hold advances once per channel, not once per subscriber
        for ch in self.fanout.live():
            if not ch.cursors:
                continue  # last cursor detached under us; reaped below
            batch = env.get(ch.gid)
            updates = (
                self._batch_updates(
                    batch, lambda r, p=ch.pq: self._decode_row(r, p)
                )
                if batch is not None
                else []
            )
            if not updates and not ch.wants_progress():
                ch.frontier = ts + 1
                continue
            entry = ch.publish(ts, updates, progress_ts=ts + 1)
            ch.frontier = ts + 1
            for sub in ch.shared_tick(entry):
                # shed (backlog/retention) or closed under us: release the
                # read hold now; the frontend reports 53400 on its next
                # drain
                if sub.state == "shed":
                    self.overload.bump("subscribe_sheds")
                self.teardown_subscription(sub.sub_id, state=sub.state)
        # reclaim ring entries every live cursor is past (hard-capped by
        # fanout_ring_ticks), then wake the reactor's stream pumps once
        self.fanout.trim()
        self.fanout.notify()
        if not self.sinks:
            return
        emit_durable = persist and self.durable and self.deploy_state == "leader"
        if self.durable and not emit_durable:
            return  # read-only generations never touch a changelog
        order = str(self.configs.get("sink_commit_order"))
        for gid, sink in self.sinks.items():
            batch = env.get(sink.from_gid)
            if batch is None:
                continue
            updates = self._batch_updates(
                batch, lambda r, s=sink: self._decode_desc_row(r, s.desc)
            )
            try:
                sink.emit(
                    updates, ts + 1,
                    self._shard(progress_shard_id(gid)) if emit_durable else None,
                    epoch=self.epoch if emit_durable else None,
                    order=order,
                )
            except Fenced:
                self.deploy_state = "fenced"
                raise

    # -- DDL -------------------------------------------------------------------
    def _create_table(self, stmt: ast.CreateTable) -> ExecResult:
        cols = tuple(
            ColumnDesc(c.name, coltype_of(c.typ), nullable=not c.not_null)
            for c in stmt.columns
        )
        desc = RelationDesc(cols)
        item = self.catalog.create(CatalogItem(stmt.name, "table", desc=desc))
        self.storage[item.global_id] = StorageCollection(desc.dtypes)
        self._persist_catalog()
        return ExecResult("status", status="CREATE TABLE")

    _AUCTION_TABLES = {
        "organizations": RelationDesc.of(
            ("id", ColType.INT64), ("name", ColType.STRING), key=(0,)
        ),
        "users": RelationDesc.of(
            ("id", ColType.INT64), ("org_id", ColType.INT64), ("name", ColType.STRING),
            key=(0,),
        ),
        "accounts": RelationDesc.of(
            ("id", ColType.INT64), ("org_id", ColType.INT64), ("balance", ColType.INT64),
            key=(0,),
        ),
        "auctions": RelationDesc.of(
            ("id", ColType.INT64), ("seller", ColType.INT64), ("item", ColType.STRING),
            ("end_time", ColType.TIMESTAMP), key=(0,),
        ),
        "bids": RelationDesc.of(
            ("id", ColType.INT64), ("buyer", ColType.INT64), ("auction_id", ColType.INT64),
            ("amount", ColType.INT64), ("bid_time", ColType.TIMESTAMP), key=(0,),
        ),
    }

    # the four tables, at the columns Q3's and Q17's texts read, that a TPC-H
    # generator describing no tables of its own emits (the benchmark's seeded
    # copy, chipbench/traffic/tpch.py); the program's own describes TPC-H's
    # eight in full (storage/generator.py::TPCH_TABLES)
    _TPCH_TABLES = {
        "customer": RelationDesc.of(
            ("c_custkey", ColType.INT64), ("c_mktsegment", ColType.STRING),
            ("c_nationkey", ColType.INT64), key=(0,),
        ),
        "orders": RelationDesc.of(
            ("o_orderkey", ColType.INT64), ("o_custkey", ColType.INT64),
            ("o_orderdate", ColType.TIMESTAMP), ("o_shippriority", ColType.INT64),
            key=(0,),
        ),
        "lineitem": RelationDesc.of(
            ("l_orderkey", ColType.INT64),
            ColumnDesc("l_extendedprice", ColType.NUMERIC, scale=2),
            ColumnDesc("l_discount", ColType.NUMERIC, scale=2),
            ("l_shipdate", ColType.TIMESTAMP), ("l_quantity", ColType.INT64),
            ("l_partkey", ColType.INT64),
        ),
        "part": RelationDesc.of(
            ("p_partkey", ColType.INT64), ("p_brand", ColType.INT64),
            ("p_container", ColType.INT64), key=(0,),
        ),
    }

    def _create_file_source(self, stmt: ast.CreateFileSource) -> ExecResult:
        """External file-tail CDC source with durable offset reclocking
        (storage/file_source.py; remap shard per reclock.rs:277)."""
        cols = tuple(
            ColumnDesc(c.name, coltype_of(c.typ), nullable=True)
            for c in stmt.columns
        )
        if stmt.envelope == "upsert":
            # validate BEFORE any catalog mutation: a bad key must not leave
            # a poisoned item that breaks every future boot
            if not stmt.key_cols:
                raise PlanError("ENVELOPE UPSERT requires KEY (cols)")
            names = {c.name for c in cols}
            for k in stmt.key_cols:
                if k not in names:
                    raise PlanError(f"upsert key column {k!r} is not in the column list")
        desc = RelationDesc(cols)
        options = (
            ("path", stmt.path),
            ("format", stmt.format),
            ("envelope", stmt.envelope),
            ("key", ",".join(stmt.key_cols)),
        )
        item = self.catalog.create(
            CatalogItem(
                stmt.name, "source", desc=desc, generator="file", options=options
            )
        )
        self.storage[item.global_id] = StorageCollection(desc.dtypes)
        self._register_file_source(item)
        self._persist_catalog()
        return ExecResult("status", status="CREATE SOURCE")

    def _register_file_source(self, item) -> None:
        """Instantiate the runtime poller; resume offset from the remap shard
        and rebuild upsert state from the rehydrated collection."""
        from ..storage.file_source import FileSourceSpec, FileTailSource
        from ..storage.upsert import UpsertState

        opts = dict(item.options)
        spec = FileSourceSpec(
            path=opts["path"],
            fmt=opts["format"],
            col_names=tuple(c.name for c in item.desc.columns),
            envelope=opts.get("envelope", "none"),
            key_cols=tuple(k for k in opts.get("key", "").split(",") if k),
        )
        src = FileTailSource(spec)
        gid = item.global_id
        if self.durable:
            # the remap shard's last binding is the resume point: offsets
            # below it are already ingested (and durable via the same txn)
            m = self._shard(f"{gid}_remap")
            _seq, state = m.fetch_state()
            if state.upper > 0:
                best = 0
                for cols_ in m.snapshot(state.upper - 1):
                    if len(cols_.get("c0", ())):
                        best = max(best, int(cols_["c0"].max()))
                src.offset = best
        upsert_state = None
        if spec.envelope == "upsert":
            upsert_state = UpsertState()
            names = list(spec.col_names)
            key_idx = [names.index(k) for k in spec.key_cols]
            val_idx = [i for i in range(len(names)) if i not in key_idx]
            store = self.storage.get(gid)
            if store is not None and getattr(store, "arr", None) is not None:
                acc: dict[tuple, int] = {}
                for data, _t, d in store.arr.rows_host():
                    acc[data] = acc.get(data, 0) + d
                from ..expr.scalar import null_sentinel

                def _stored(i, x):
                    # rows_host maps float NaN (the NULL sentinel) to None;
                    # upsert state stores raw storage values, so map it back
                    if x is None:
                        return null_sentinel(item.desc.columns[i].dtype)
                    return x

                for data, cnt in acc.items():
                    if cnt > 0:
                        k = tuple(_stored(i, data[i]) for i in key_idx)
                        v = tuple(_stored(i, data[i]) for i in val_idx)
                        upsert_state.state[k] = v
        if not hasattr(self, "file_sources"):
            self.file_sources = []
        self.file_sources.append((src, gid, upsert_state))

    def _create_source(self, stmt: ast.CreateSource) -> ExecResult:
        opts = dict(stmt.options)
        if stmt.generator == "auction":
            gen = AuctionGenerator(seed=0, dict_=self.catalog.dict)
            tables = self._AUCTION_TABLES
        elif stmt.generator == "key_value":
            from ..storage.upsert import KeyValueGenerator

            gen = KeyValueGenerator(
                keys=int(opts.get("keys", 100) or 100),
                seed=int(opts.get("seed", 0) or 0),
            )
            tables = {
                "key_value": RelationDesc.of(
                    ("key", ColType.INT64), ("value", ColType.INT64), key=(0,)
                )
            }
        elif stmt.generator == "counter":
            maxc = opts.get("max cardinality")
            gen = CounterGenerator(int(maxc) if maxc else None)
            tables = {"counter": RelationDesc.of(("counter", ColType.INT64))}
        elif stmt.generator == "tpch":
            sf = float(opts.get("scale factor", 0.01) or 0.01)
            from ..storage.generator import _SEGMENTS

            codes = [self.catalog.dict.encode(seg) for seg in _SEGMENTS]
            gen = TpchGenerator(sf=sf, segment_codes=codes)
            describe = getattr(gen, "tables", None)
            tables = self._TPCH_TABLES if describe is None else describe(self.catalog.dict)
        else:
            raise PlanError(f"unsupported load generator {stmt.generator}")
        append_only = stmt.generator == "auction" or (
            stmt.generator == "counter" and not opts.get("max cardinality")
        )
        gids = {}
        for tname, desc in tables.items():
            item = self.catalog.create(
                CatalogItem(tname, "source", desc=desc, append_only=append_only)
            )
            self.storage[item.global_id] = StorageCollection(desc.dtypes)
            gids[tname] = item.global_id
        self.catalog.create(CatalogItem(stmt.name, "source_parent", generator=stmt.generator))
        self.generators.append((gen, gids))
        if stmt.generator == "auction":
            ts = self.oracle.write_ts()
            for tname, cols in gen.static_tables().items():
                n = len(cols[0])
                batch = UpdateBatch.build((), cols, np.full(n, ts), np.ones(n, dtype=np.int64))
                self._apply_writes({gids[tname]: batch}, ts)
        elif stmt.generator == "tpch":
            ts = self.oracle.write_ts()
            init = gen.initial_batches(ts)
            self._apply_writes({gids[t]: b for t, b in init.items()}, ts)
        self._persist_catalog()
        return ExecResult("status", status="CREATE SOURCE")

    def _create_view(self, stmt: ast.CreateView) -> ExecResult:
        pq = self.planner.plan_query(stmt.query)
        self.catalog.create(
            CatalogItem(stmt.name, "view", desc=pq.desc, query_ast=stmt.query, mir=pq)
        )
        self._persist_catalog()
        return ExecResult("status", status="CREATE VIEW")

    def _create_materialized_view(self, stmt: ast.CreateMaterializedView) -> ExecResult:
        pq = self.planner.plan_query(stmt.query)
        rel = pq.mir
        if pq.finishing.limit is not None:
            from ..sql.plan import _apply_finishing_as_topk

            rel = _apply_finishing_as_topk(pq)
        rel = optimize(rel, self.configs)
        item = self.catalog.create(
            CatalogItem(stmt.name, "materialized_view", desc=pq.desc, query_ast=stmt.query)
        )
        try:
            return self._install_mv(item, pq, rel)
        except Exception:
            # install is transactional against the shared-trace registry and
            # in-memory state: a CREATE that fails after exporting a trace
            # must not leak the export (a later dataflow would import a
            # stale, reader-less trace). CrashPointReached is a
            # BaseException and deliberately skips this — crash recovery
            # converges via boot, not via in-process cleanup.
            self._rollback_mv_install(item)
            raise

    def _install_mv(self, item: CatalogItem, pq, rel) -> ExecResult:
        gid = item.global_id
        src_gids = sorted(_collect_gets(rel))
        env = {g: self.storage[g].dtypes for g in src_gids}
        desc = lower_to_dataflow(
            gid, rel, env, src_gids, index_key=(), as_of=0, mono_ids=self._mono_ids()
        )
        # hydrate: snapshot all inputs at the current read timestamp
        as_of = self.oracle.read_ts()
        desc.as_of = as_of
        snaps = {g: self.storage[g].snapshot(as_of) for g in src_gids}
        df = self._make_dataflow(desc, snaps, trace_reader=gid)
        results = df.step(as_of, snaps)
        self.storage[gid] = StorageCollection(pq.desc.dtypes)
        out = results.get(gid)
        item.mir = rel
        # in-memory state completes FIRST: a transient persist failure below
        # must leave a fully functional MV (dataflow installed, storage
        # hydrated), not a durable catalog entry whose view never updates
        if out is not None and out[0] is not None:
            self.storage[gid].append(out[0], as_of)
        self.dataflows.append((gid, df, src_gids))
        # then catalog before hydration (the _apply_writes ordering rule: a
        # crash between the two persists must leave an MV the next boot can
        # see and reconcile — the reverse order would orphan a hydrated
        # shard whose gid a retried CREATE re-allocates)
        self._persist_catalog()
        if self.durable and out is not None and out[0] is not None:
            # the hydration snapshot goes to the DURABLE shard too: the
            # shard is what external readers (clusterd, fsck, the crash
            # matrix) see, and it must never start life diverged from the
            # in-memory collection (crash-matrix finding; a failure here
            # heals at the next boot's _reconcile_mv_shard)
            self._persist_batches({gid: out[0]}, as_of)
        return ExecResult("status", status="CREATE MATERIALIZED VIEW")

    def _rollback_mv_install(self, item: CatalogItem) -> None:
        """Undo a failed CREATE MATERIALIZED VIEW: in-memory state, the
        dataflow, and — crucially — any shared-trace exports/holds the
        render registered, leaving the TraceManager exactly as before."""
        gid = item.global_id
        self.catalog.items.pop(item.name, None)
        self.storage.pop(gid, None)
        self.dataflows = [d for d in self.dataflows if d[0] != gid]
        self.trace_manager.rollback_install(gid)
        if self.durable and self.deploy_state == "leader":
            try:
                # scrub the item from the durable catalog if the install got
                # far enough to persist it; best-effort — a boot that still
                # sees the item just reinstalls the MV, which is the
                # pre-rollback contract for partial CREATEs
                self._persist_catalog()
            except Exception:
                pass

    def _create_index(self, stmt: ast.CreateIndex) -> ExecResult:
        on = self.catalog.get(stmt.on)
        key = tuple(on.desc.index_of(c) for c in stmt.key_columns) if stmt.key_columns else tuple(on.desc.key)
        name = stmt.name or f"{stmt.on}_idx"
        self.catalog.create(
            CatalogItem(name, "index", index_on=stmt.on, index_key=key)
        )
        self._persist_catalog()
        return ExecResult("status", status="CREATE INDEX")

    def _drop(self, stmt: ast.DropObject) -> ExecResult:
        item = self.catalog.drop(stmt.name, stmt.if_exists)
        if item is not None:
            self.storage.pop(item.global_id, None)
            self.dataflows = [d for d in self.dataflows if d[0] != item.global_id]
            # release the dropped dataflow's since holds: shared traces it
            # read re-arm compaction to the next-slowest reader, and a trace
            # left with NO readers is deleted (nobody would maintain it)
            self.trace_manager.release(item.global_id)
            if hasattr(self, "file_sources"):
                self.file_sources = [
                    e for e in self.file_sources if e[1] != item.global_id
                ]
            # egress teardown: subscriptions tailing the dropped collection
            # end cleanly; a sink riding on it is dropped with it (its
            # progress shard stays — orphaned history is harmless)
            for sid, sub in list(self.subscriptions.items()):
                if sub.gid == item.global_id:
                    self.subscriptions.pop(sid, None)
                    sub.close("dropped")
            self.sinks.pop(item.global_id, None)
            for dep_name, dep in list(self.catalog.items.items()):
                if dep.kind == "sink" and dict(dep.options).get("from") == item.name:
                    self.catalog.items.pop(dep_name, None)
                    self.sinks.pop(dep.global_id, None)
        self._persist_catalog()
        return ExecResult("status", status=f"DROP {stmt.kind.upper()}")

    # -- DML -------------------------------------------------------------------
    def _insert(self, stmt: ast.Insert) -> ExecResult:
        item = self.catalog.get(stmt.table)
        if item.kind != "table":
            raise PlanError(f"cannot INSERT into {item.kind} {stmt.table}")
        desc = item.desc
        if stmt.columns:
            positions = [desc.index_of(c) for c in stmt.columns]
        else:
            positions = list(range(desc.arity))
        cols = [[] for _ in range(desc.arity)]
        for row in stmt.rows:
            if len(row) != len(positions):
                raise PlanError("INSERT row arity mismatch")
            vals = [None] * desc.arity
            for pos, e in zip(positions, row):
                vals[pos] = self._literal_value(e, desc.columns[pos])
            for i, v in enumerate(vals):
                if v is None:
                    # unmentioned column: SQL default is NULL
                    from ..expr.scalar import null_sentinel

                    v = null_sentinel(desc.columns[i].dtype)
                cols[i].append(v)
        arrays = tuple(
            np.array(c, dtype=desc.columns[i].dtype) for i, c in enumerate(cols)
        )
        ts = self.oracle.write_ts()
        n = len(stmt.rows)
        batch = UpdateBatch.build((), arrays, np.full(n, ts), np.ones(n, dtype=np.int64))
        self._apply_writes({item.global_id: batch}, ts)
        return ExecResult("status", status=f"INSERT 0 {n}")

    def _delete(self, stmt: ast.Delete) -> ExecResult:
        item = self.catalog.get(stmt.table)
        if item.kind != "table":
            raise PlanError(f"cannot DELETE from {item.kind}")
        # evaluate SELECT * FROM t WHERE pred, emit retractions
        q = ast.Query(
            ast.Select(
                items=(ast.SelectItem(ast.Star()),),
                from_=(ast.TableRef(stmt.table),),
                where=stmt.where,
            )
        )
        res = self._select(q)
        if not res.rows:
            return ExecResult("status", status="DELETE 0")
        desc = item.desc
        cols = tuple(
            np.array(
                [self._encode_val(r[i], desc.columns[i]) for r in res.rows],
                dtype=desc.columns[i].dtype,
            )
            for i in range(desc.arity)
        )
        ts = self.oracle.write_ts()
        n = len(res.rows)
        batch = UpdateBatch.build((), cols, np.full(n, ts), -np.ones(n, dtype=np.int64))
        self._apply_writes({item.global_id: batch}, ts)
        return ExecResult("status", status=f"DELETE {n}")

    def _traces(self):
        """The shared-trace registry, or None when arrangement sharing is
        force-disabled (enable_arrangement_sharing, the bisection dyncfg)."""
        if not bool(self.configs.get("enable_arrangement_sharing")):
            return None
        return self.trace_manager

    def _make_dataflow(self, desc, snaps: dict | None = None, trace_reader=None):
        """Render a DataflowDescription through the shared rendering decision
        point (`runtime.render_dataflow`): the fused single-program path when
        enabled and expressible — over a device mesh per `exchange_backend` —
        else the host-orchestrated operator graph (the rendering-choice
        analogue of ENABLE_MZ_JOIN_CORE)."""
        from ..dataflow.fused import FusedCaps
        from ..dataflow.runtime import render_dataflow

        caps = FusedCaps(
            ratio=int(self.configs.get("lsm_merge_ratio")),
            cap_ratio=int(self.configs.get("fused_join_cap_ratio")),
        )
        # pre-size so the hydration tick doesn't ladder through doubling
        # retries on large input snapshots
        snap_rows = max((int(b.count()) for b in (snaps or {}).values()), default=0)
        return render_dataflow(
            desc,
            fused=bool(self.configs.get("enable_fused_render")),
            exchange_backend=str(self.configs.get("exchange_backend")),
            mesh=self.mesh,
            caps=caps,
            traces=self._traces() if trace_reader is not None else None,
            trace_reader=trace_reader,
            operator_logging=bool(self.configs.get("enable_operator_logging")),
            snap_rows=snap_rows,
        )

    def _encode_val(self, v, cd):
        """Re-encode a decoded row value to its storage representation:
        strings to dictionary codes, NUMERIC floats back to fixed-point,
        None back to the dtype's NULL sentinel. Decoded SELECT rows carry
        NUMERIC as scaled floats; retractions and rewrites must target the
        stored fixed-point value exactly."""
        if v is None:
            from ..expr.scalar import null_sentinel

            return null_sentinel(cd.dtype)
        if isinstance(v, str):
            return self.catalog.dict.encode(v)
        if cd.typ == ColType.NUMERIC and isinstance(v, float):
            return int(round(v * 10**cd.scale))
        return v

    def _update(self, stmt: ast.Update) -> ExecResult:
        """UPDATE = retract matching rows + insert modified versions (the
        read-then-write shape of the reference's sequence_update)."""
        item = self.catalog.get(stmt.table)
        if item.kind != "table":
            raise PlanError(f"cannot UPDATE {item.kind}")
        q = ast.Query(
            ast.Select(
                items=(ast.SelectItem(ast.Star()),),
                from_=(ast.TableRef(stmt.table),),
                where=stmt.where,
            )
        )
        res = self._select(q)
        if not res.rows:
            return ExecResult("status", status="UPDATE 0")
        desc = item.desc
        assign = {col: e for col, e in stmt.assignments}
        encode_val = self._encode_val
        old_cols = [[] for _ in range(desc.arity)]
        new_cols = [[] for _ in range(desc.arity)]
        from ..sql.plan import Scope, ScopeCol, PType

        scope = Scope(
            [
                ScopeCol(stmt.table, c.name, PType(c.typ, c.scale if c.typ == ColType.NUMERIC else 0))
                for c in desc.columns
            ]
        )
        for row in res.rows:
            encoded = [encode_val(v, desc.columns[i]) for i, v in enumerate(row)]
            for i in range(desc.arity):
                old_cols[i].append(encoded[i])
            # evaluation happens in None-space (decoded rows carry None for
            # NULL) so the interpreter never has to guess sentinel widths;
            # results re-encode (None -> sentinel) below
            eval_row = [
                None if row[i] is None else encoded[i] for i in range(desc.arity)
            ]
            newrow = list(encoded)
            for i, c in enumerate(desc.columns):
                if c.name in assign:
                    # evaluate assignment expression against the OLD row
                    e, _t = self.planner.plan_scalar(assign[c.name], scope)
                    newrow[i] = encode_val(_eval_scalar_on_row(e, eval_row), c)
            for i in range(desc.arity):
                new_cols[i].append(newrow[i])
        import numpy as _np

        ts = self.oracle.write_ts()
        n = len(res.rows)
        arrays = tuple(
            _np.concatenate([
                _np.array(old_cols[i], dtype=desc.columns[i].dtype),
                _np.array(new_cols[i], dtype=desc.columns[i].dtype),
            ])
            for i in range(desc.arity)
        )
        diffs = _np.concatenate([-_np.ones(n, dtype=_np.int64), _np.ones(n, dtype=_np.int64)])
        batch = UpdateBatch.build((), arrays, _np.full(2 * n, ts), diffs)
        self._apply_writes({item.global_id: batch}, ts)
        return ExecResult("status", status=f"UPDATE {n}")

    def _literal_value(self, e, cdesc: ColumnDesc):
        if isinstance(e, ast.Param):
            # extended-protocol parameter: re-dispatch the bound text value
            # as the equivalent literal AST (typed by the target column)
            ps = self.planner._params
            if ps is None or not (1 <= e.index <= len(ps)):
                raise PlanError(f"parameter ${e.index} not bound")
            v = ps[e.index - 1]
            if v is None:
                return self._literal_value(ast.NullLit(), cdesc)
            if cdesc.typ == ColType.STRING:
                return self.catalog.dict.encode(v)
            if cdesc.typ == ColType.JSONB:
                return self.catalog.dict.encode(self._json_canonical(v))
            if cdesc.typ == ColType.BOOL:
                return v.lower() in ("t", "true", "1")
            import re as _re

            if _re.fullmatch(r"\d{4}-\d{2}-\d{2}", v):
                return self._literal_value(ast.DateLit(v), cdesc)
            return self._literal_value(ast.NumberLit(v.lstrip("+")), cdesc)
        if isinstance(e, ast.NullLit):
            from ..expr.scalar import null_sentinel

            return null_sentinel(cdesc.dtype)
        if cdesc.typ == ColType.STRING and isinstance(
            e, (ast.NumberLit, ast.BoolLit)
        ):
            # coerce non-string literals into text columns (pg casts them)
            v = e.value if isinstance(e, ast.NumberLit) else str(e.value).lower()
            return self.catalog.dict.encode(str(v))
        if isinstance(e, ast.NumberLit):
            if "e" in e.value or "E" in e.value:  # scientific notation
                # expand the exponent exactly and reuse the plain-decimal
                # path, so '2.678' and '2.678e0' encode identically
                # (truncation, not rounding — advisor r4)
                from decimal import Decimal

                txt = format(Decimal(e.value), "f")
                if cdesc.typ in (ColType.INT64, ColType.INT32):
                    return int(Decimal(e.value))
                return self._literal_value(ast.NumberLit(txt), cdesc)
            if cdesc.typ == ColType.NUMERIC:
                if "." in e.value:
                    # sign applies to the WHOLE value: int('-1')*100 + 50 would
                    # yield -50 for '-1.50' instead of -150
                    neg = e.value.lstrip().startswith("-")
                    ip, fp = e.value.lstrip().lstrip("-").split(".")
                    fp = (fp + "0" * cdesc.scale)[: cdesc.scale]
                    mag = int(ip or "0") * 10**cdesc.scale + int(fp or "0")
                    return -mag if neg else mag
                return int(e.value) * 10**cdesc.scale
            if "." in e.value:
                # f32 like plan.py's literal typing — host and device agree
                return float(np.float32(e.value))
            return int(e.value)
        if isinstance(e, ast.StringLit):
            if cdesc.typ == ColType.JSONB:
                return self.catalog.dict.encode(self._json_canonical(e.value))
            return self.catalog.dict.encode(e.value)
        if isinstance(e, ast.BoolLit):
            return e.value
        if isinstance(e, ast.UnaryOp) and e.op == "-":
            v = self._literal_value(e.expr, cdesc)
            return -v
        if isinstance(e, ast.DateLit):
            from ..storage.generator import date_num

            y, m, d = (int(x) for x in e.value.split("-"))
            return int(date_num(y, m, d))
        raise PlanError(f"unsupported literal {e!r}")

    def _json_canonical(self, text: str) -> str:
        from ..expr.strings import json_canonical

        try:
            return json_canonical(text)
        except ValueError as exc:
            raise PlanError(f"invalid input syntax for type jsonb: {exc}") from exc

    # -- durability ------------------------------------------------------------
    def _shard(self, gid: str):
        from ..persist import ShardMachine

        m = self.shards.get(gid)
        if m is None:
            m = ShardMachine(self.blob, self.consensus, gid)
            self.shards[gid] = m
        return m

    def _persist_catalog(self) -> None:
        """Write the durable catalog (reference: persist-backed catalog shard,
        src/catalog/src/durable). Pickled: single-node durability; a
        proto/json codec slots in here for cross-version upgrades."""
        if not self.durable:
            return
        import pickle

        items = []
        for it in self.catalog.items.values():
            if it.kind == "introspection":
                continue
            items.append(
                {
                    "name": it.name,
                    "kind": it.kind,
                    "desc": it.desc,
                    "query_ast": it.query_ast,
                    "index_on": it.index_on,
                    "index_key": it.index_key,
                    "generator": it.generator,
                    "options": it.options,
                    "global_id": it.global_id,
                    "append_only": it.append_only,
                }
            )
        from ..persist import CATALOG_VERSION

        doc = pickle.dumps(
            {
                # format version stamp: _boot migrates older docs forward and
                # REFUSES docs stamped by a newer build (a downgrade must
                # fail loudly, not misread the catalog)
                "version": CATALOG_VERSION,
                "items": items,
                "strings": list(self.catalog.dict._strs),
                "ts": self.oracle.read_ts(),
                "generators": pickle.dumps(self.generators),
                "next_id": self.catalog._next_id,
            }
        )
        for _ in range(8):
            head = self.consensus.head("catalog")
            seq = head.seqno if head is not None else None
            if self.consensus.compare_and_set("catalog", seq, doc):
                self._persisted_dict_len = len(self.catalog.dict)
                return
        raise RuntimeError("catalog CAS contention")

    def checkpoint(self) -> None:
        """Persist catalog + generator progress (clean-shutdown durability for
        load-generator sources; table/MV data is crash-consistent via shards)."""
        self._persist_catalog()

    def _boot(self, read_only: bool = False) -> None:
        """Restart: reload catalog, rehydrate storage, re-render dataflows.

        Re-entrant by construction: every step is idempotent (txn apply
        checks shard uppers, rehydration reads, MV reconciliation diffs), so
        a crash ANYWHERE in here converges on the next boot — the
        crash-during-recovery half of the crash matrix. `read_only`
        (preflight/catching-up instances) skips the one writing step, the
        durable MV reconciliation."""
        import itertools
        import pickle

        head = self.consensus.head("catalog")
        if head is None:
            return
        # version gate BEFORE any recovery work: a catalog stamped by a
        # newer build must refuse to boot without touching anything
        doc = _migrate_catalog_doc(pickle.loads(head.data))
        # txn-wal recovery FIRST: a crash between a multi-shard commit's
        # txns append and its apply must not leave data shards behind the log
        self._txn_machine().apply_up_to(1 << 62)
        self.catalog._next_id = doc["next_id"]
        for s in doc["strings"]:
            self.catalog.dict.encode(s)
        self.oracle.apply_write(doc["ts"])
        self.catalog._ids = itertools.count(doc["next_id"])
        self.generators = pickle.loads(doc["generators"])
        mvs = []
        sink_items = []
        gen_gids: dict[str, str] = {}
        for d in doc["items"]:
            item = CatalogItem(
                d["name"], d["kind"], desc=d["desc"], query_ast=d["query_ast"],
                index_on=d["index_on"], index_key=d["index_key"],
                generator=d["generator"], options=d["options"],
                global_id=d["global_id"], append_only=d.get("append_only", False),
            )
            self.catalog.items[item.name] = item
            if item.kind in ("table", "source"):
                self.storage[item.global_id] = StorageCollection(item.desc.dtypes)
                self._rehydrate_collection(item.global_id)
                if item.generator == "file":
                    self._register_file_source(item)
            elif item.kind == "view":
                item.mir = self.planner.plan_query(item.query_ast)
            elif item.kind == "materialized_view":
                mvs.append(item)
            elif item.kind == "sink":
                sink_items.append(item)
        # regenerate generator gid maps from table names (stored order kept)
        for gen, gids in self.generators:
            for t in list(gids):
                gids[t] = self.catalog.get(t).global_id
        # reads must observe every committed shard write, even ones after the
        # last catalog persist: advance the oracle to the max shard upper
        for d in doc["items"]:
            if d["kind"] in ("table", "source", "materialized_view"):
                up = self._shard(d["global_id"]).upper()
                if up > 0:
                    self.oracle.apply_write(up - 1)
        for item in mvs:
            self.storage[item.global_id] = StorageCollection(item.desc.dtypes)
            self._reinstall_mv(item, reconcile=not read_only)
        # shard reconciliation may have minted correction times beyond the
        # pre-boot read frontier: every dataflow must observe time passing
        # or a peek at the new read_ts errors as incomplete
        ts = self.oracle.read_ts()
        for mv_gid, df, _src in self.dataflows:
            if df.frontier <= ts:
                if df.has_temporal:
                    # temporal dataflows emit real deltas (window expiries
                    # due in (as_of, ts]) when time passes — append them to
                    # storage and the durable shard exactly as the quiet
                    # path of _apply_writes would, not just bump the
                    # frontier (dropping them would bake expired rows into
                    # the collection external readers hydrate)
                    results = df.step(ts, {})
                    out = results.get(mv_gid)
                    if out is not None and out[0] is not None:
                        self.storage[mv_gid].append(out[0], ts)
                        if not read_only:
                            m = self._shard(mv_gid)
                            lower = m.upper()
                            if lower < ts + 1:
                                # epoch=None: pre-leadership, like
                                # _reconcile_mv_shard
                                m.compare_and_append(
                                    _batch_to_cols(out[0]), lower, ts + 1
                                )
                else:
                    df.frontier = ts + 1
        # sinks last: resume's re-derivation reads source shards, which are
        # final only after MV reconciliation and the temporal fix-ups above
        for item in sink_items:
            self._register_sink(item, resume=not read_only)

    def _rehydrate_collection(self, gid: str) -> None:
        from ..persist import ShardMachine

        m = self._shard(gid)
        _seq, state = m.fetch_state()
        if state.upper <= state.since and not state.batches:
            return
        store = self.storage[gid]
        # upper-1 is the newest complete time; since ≤ upper-1 is a shard
        # invariant (downgrade_since caps), so this read is always definite
        for cols in m.snapshot(max(state.upper - 1, 0)):
            data = [cols[f"c{i}"] for i in range(len(store.dtypes))]
            batch = UpdateBatch.build((), tuple(data), cols["times"], cols["diffs"])
            store.arr.insert(batch)
        store.upper = state.upper

    def _reinstall_mv(self, item: CatalogItem, reconcile: bool = True) -> None:
        """Re-plan + re-render an MV and hydrate from input snapshots."""
        from ..sql.lower import lower_to_dataflow as _lower
        from ..transform import optimize as _opt

        pq = self.planner.plan_query(item.query_ast)
        rel = pq.mir
        if pq.finishing.limit is not None:
            from ..sql.plan import _apply_finishing_as_topk

            rel = _apply_finishing_as_topk(pq)
        rel = _opt(rel)
        item.mir = rel
        gid = item.global_id
        src_gids = sorted(_collect_gets(rel))
        env = {g: self.storage[g].dtypes for g in src_gids}
        desc = _lower(
            gid, rel, env, src_gids, index_key=(), as_of=0, mono_ids=self._mono_ids()
        )
        as_of = self.oracle.read_ts()
        desc.as_of = as_of
        snaps = {g: self.storage[g].snapshot(as_of) for g in src_gids}
        df = self._make_dataflow(desc, snaps, trace_reader=gid)
        results = df.step(as_of, snaps)
        out = results.get(gid)
        if out is not None and out[0] is not None:
            self.storage[gid].append(out[0], as_of)
        self.dataflows.append((gid, df, src_gids))
        if reconcile:
            self._reconcile_mv_shard(gid, as_of)

    def _reconcile_mv_shard(self, gid: str, as_of: int) -> None:
        """Boot-time self-correction of an MV's DURABLE shard.

        The in-memory collection is recomputed from base snapshots at boot,
        so it is always right — but the durable shard is appended as a side
        effect of each tick, and a crash between the base-shard commit and
        the derived persist leaves it missing that tick's delta FOREVER:
        the in-tick `_mv_sink_correct` diffs desired against the (correct,
        recomputed) memory collection and finds nothing to heal. Found by
        the crash matrix; fixed by diffing desired against the SHARD here
        and appending one correction, exactly like the reference's
        self-correcting persist_sink but at boot. Idempotent (an empty diff
        appends nothing), so a crash mid-reconciliation just reruns it."""
        m = self._shard(gid)
        _seq, state = m.fetch_state()
        desired = self.storage[gid].snapshot(as_of)
        persisted_cols = (
            m.snapshot(max(state.upper - 1, 0)) if state.upper > 0 else []
        )
        if not persisted_cols and desired.count() == 0:
            return  # both empty: nothing to reconcile
        store = self.storage[gid]
        persisted = [
            UpdateBatch.build(
                (),
                tuple(cols[f"c{i}"] for i in range(len(store.dtypes))),
                cols["times"],
                cols["diffs"],
            )
            for cols in persisted_cols
        ]
        t_corr = max(as_of, state.upper)
        correction = self._diff_correction(desired, persisted, t_corr)
        n = int(correction.count())
        if not n:
            return
        _log.warn(
            "boot mv shard reconciliation: durable shard diverged from "
            "its recomputed view; healing",
            shard=gid,
            rows=n,
        )
        # epoch=None: reconciliation runs pre-leadership (before the fence
        # bump); read_only boots skip it entirely
        m.compare_and_append(_batch_to_cols(correction), state.upper, t_corr + 1)
        self.oracle.apply_write(t_corr)

    def _diff_correction(self, desired, persisted: list, t: int):
        """(desired − Σ persisted) advanced to `t`, consolidated: the one
        correction-delta kernel behind both self-correction paths (the
        in-tick _mv_sink_correct and boot's _reconcile_mv_shard). The crash
        matrix's mv_shard_divergence deliberately does NOT share this code —
        an independent host-side implementation is what makes it a check."""
        from ..dataflow.runtime import negate_batch
        from ..ops.consolidate import advance_times, consolidate

        merged = desired
        for p in persisted:
            merged = UpdateBatch.concat(merged, negate_batch(p))
        return consolidate(advance_times(merged, t))

    def _mono_ids(self) -> set:
        return {
            i.global_id for i in self.catalog.items.values() if i.append_only
        }

    # -- 0dt deployment --------------------------------------------------------
    def _take_leadership(self) -> None:
        """Become the writing generation: bump the leader epoch and fence
        every shard so the previous generation's next write raises Fenced."""
        import json as _json

        for _ in range(8):
            head = self.consensus.head("leader")
            cur = _json.loads(head.data)["epoch"] if head is not None else 0
            self.epoch = cur + 1
            doc = _json.dumps({"epoch": self.epoch}).encode()
            if self.consensus.compare_and_set(
                "leader", head.seqno if head is not None else None, doc
            ):
                break
        else:
            raise RuntimeError("leader CAS contention")
        from ..egress import progress_shard_id

        for item in self.catalog.items.values():
            if item.kind in ("table", "source", "materialized_view"):
                self._shard(item.global_id).fence(self.epoch)
            elif item.kind == "sink":
                # sink progress registers are commit points too: fence them
                # so a zombie generation cannot double-commit a frame
                self._shard(progress_shard_id(item.global_id)).fence(self.epoch)
        if self.durable:
            # the txns shard is a commit point too: fence it so a zombie
            # generation's multi-shard commit fails at its linearization CAS
            self._txn_machine().txns.fence(self.epoch)
        self.deploy_state = "leader"

    def catch_up(self) -> int:
        """Preflight: pull new shard data into local state (read-only).
        Returns the number of commits applied."""
        from ..persist import ShardMachine

        per_time: dict[int, dict[str, UpdateBatch]] = {}
        for item in list(self.catalog.items.values()):
            if item.kind not in ("table", "source"):
                continue
            gid = item.global_id
            store = self.storage[gid]
            m = self._shard(gid)
            batches, upper = m.listen_from(store.upper)
            import numpy as _np

            for cols in batches:
                for t in _np.unique(cols["times"]):
                    mask = cols["times"] == t
                    data = [
                        cols[f"c{i}"][mask] for i in range(len(store.dtypes))
                    ]
                    b = UpdateBatch.build(
                        (), tuple(data), cols["times"][mask], cols["diffs"][mask]
                    )
                    per_time.setdefault(int(t), {})[gid] = b
        for t in sorted(per_time):
            self.oracle.apply_write(t)
            self._apply_writes(per_time[t], t, persist=False)
        return len(per_time)

    def promote(self) -> None:
        """Finish a 0dt handoff: final catch-up, then take leadership
        (ReadyToPromote → IsLeader)."""
        from ..egress import progress_shard_id

        self.catch_up()
        self._take_leadership()
        # egress catch-up: frames for ticks the old leader committed while
        # this generation was read-only. Sinks only emit as leader, so this
        # closes the [sink.frontier, source upper) gap exactly once — the
        # per-tick emit below assumes frontier is always current
        for gid, sink in self.sinks.items():
            sink.resume(
                self._shard(progress_shard_id(gid)),
                lambda lo, hi, s=sink: self._sink_derive(s, lo, hi),
                epoch=self.epoch,
                order=str(self.configs.get("sink_commit_order")),
            )

    # -- write propagation -----------------------------------------------------
    def _apply_writes(
        self,
        writes: dict[str, UpdateBatch],
        ts: int,
        persist: bool = True,
        extra_shards: dict | None = None,
        on_durable=None,
    ) -> None:
        """Group commit: append to storage (and persist shards), then flow
        through every installed dataflow in dependency order (an MV's output
        delta becomes visible to downstream MVs at the same timestamp)."""
        if persist and self.durable and self.deploy_state != "leader":
            raise PlanError(
                f"read-only: this instance is {self.deploy_state}, not the leader"
            )
        from ..utils.memory_limiter import MemoryLimiter

        limit = int(self.configs.get("memory_limit_mb"))
        if limit:
            MemoryLimiter(limit).check()
        env = dict(writes)
        # Durability first: base-table writes hit their shards BEFORE any
        # in-memory state is touched, so a fenced/failed CAS can never leave
        # this process serving phantom writes that were never made durable.
        # Derived MV shards are persisted after stepping; they are recomputable
        # from the base shards on restart (the reference's persist_sink is
        # likewise self-correcting against shard contents). The catalog (with
        # the string dictionary) goes first of all: batches may reference
        # freshly minted dictionary codes, which must never outrun the durable
        # dictionary that decodes them.
        if persist and self.durable:
            if len(self.catalog.dict) != getattr(self, "_persisted_dict_len", -1):
                self._persist_catalog()
            # base-table writes are the atomicity boundary: multi-shard
            # statements commit through txn-wal (all-or-nothing); derived MV
            # shards below stay direct appends — they are recomputable and
            # self-correcting from the base shards (reference stance:
            # txn-wal fronts tables, persist_sink self-corrects).
            # extra_shards: raw column payloads (source remap bindings) that
            # must commit atomically WITH the data they reclock.
            self._persist_batches(
                writes,
                ts,
                atomic=len(writes) + len(extra_shards or {}) > 1,
                extra_shards=extra_shards,
            )
            # The durable commit point has passed: let the caller advance
            # source offsets/upsert state NOW. A failure below (dataflow
            # step, MV persist) must NOT roll sources back to re-ingest
            # records the base shards already durably hold (advisor r2).
            if on_durable is not None:
                on_durable()
        for gid, batch in writes.items():
            self.storage[gid].append(batch, ts)
        # Without durability the in-memory base-table append IS the commit
        # point; firing earlier would drop polled records forever if the
        # append itself failed (nothing durable exists to recover them from).
        if on_durable is not None and not (persist and self.durable):
            on_durable()
        interval = int(self.configs.get("mv_sink_self_correct_interval"))
        correct = interval > 0 and ts % interval == 0
        corrections: dict[str, UpdateBatch] = {}
        for mv_gid, df, src_gids in self.dataflows:
            deltas = {g: env[g] for g in src_gids if g in env}
            if not deltas and not df.has_temporal:
                # quiet dataflow; temporal ones must still see time pass —
                # but sink correction still runs (an idle view's corrupted
                # collection must heal even with no source deltas)
                df.frontier = ts + 1
                if correct:
                    corr = self._mv_sink_correct(mv_gid, df, ts)
                    if corr is not None:
                        corrections[mv_gid] = corr
                continue
            _t0 = _monotonic()
            with TRACER.span("dataflow.tick"):  # parent of the render's operator spans
                results = df.step(ts, deltas)
            _TICK_NS.observe((_monotonic() - _t0) * 1e9, dataflow=mv_gid)
            out = results.get(mv_gid)
            if out is not None and out[0] is not None:
                env[mv_gid] = out[0]
                self.storage[mv_gid].append(out[0], ts)
            if correct:
                corr = self._mv_sink_correct(mv_gid, df, ts)
                if corr is not None:
                    corrections[mv_gid] = corr
        self._drive_compaction(ts)
        if persist and self.durable:
            derived = {g: b for g, b in env.items() if g not in writes}
            # heal the DURABLE shard too: a correction must reach persist,
            # or external readers keep building on the corrupt baseline
            for gid, corr in corrections.items():
                derived[gid] = (
                    UpdateBatch.concat(derived[gid], corr)
                    if gid in derived
                    else corr
                )
            if derived:
                self._persist_batches(derived, ts)
            if len(self.catalog.dict) != getattr(self, "_persisted_dict_len", -1):
                self._persist_catalog()
        if self.subscriptions or self.sinks:
            # egress runs LAST: every durable write for this tick has landed,
            # so sink progress never commits ahead of its source shard, and
            # subscriptions see corrections merged into the tick's deltas
            egress_env = dict(env)
            for gid, corr in corrections.items():
                egress_env[gid] = (
                    UpdateBatch.concat(egress_env[gid], corr)
                    if gid in egress_env
                    else corr
                )
            self._egress_tick(egress_env, ts, persist)

    def _mv_sink_correct(self, mv_gid: str, df, ts: int):
        """Self-correcting persist sink: append (desired − persisted) at `ts`.

        `desired` is the dataflow's own index trace — the authoritative view
        contents; `persisted` is the storage collection readers see. In a
        healthy check the diff consolidates to nothing and no append
        happens; any divergence (a corrupted collection, a lost append, an
        external writer) is healed with one correction delta, bounding the
        blast radius exactly like the reference's persist_sink
        (src/compute/src/sink/materialized_view.rs:9-37). Uses the engine's
        own negate+consolidate kernels, so the diff is one device program.
        The full-snapshot diff costs O(view), so it runs every
        `mv_sink_self_correct_interval` ticks, not every tick. Returns the
        correction batch (also for durable persistence) or None.

        Durability contract: the in-memory collection is the shard's mirror
        (appends hit both; reboot rebuilds memory FROM the shard), so the
        common-mode corruption — bad output deltas appended to both, the
        reference's primary case — gets one correction that heals both.
        A divergence confined to one side converges after the next
        rehydration: reboot resets memory to the shard's contents, and the
        following interval check diffs the recomputed desired state against
        them, healing the shard too.
        """
        idx = f"idx_{mv_gid}"
        if idx not in df.index_traces or mv_gid not in self.storage:
            return None
        desired = df.index_traces[idx].merged()
        persisted = self.storage[mv_gid].snapshot(ts)
        correction = self._diff_correction(desired, [persisted], ts)
        n = int(correction.count())
        if not n:
            return None
        from ..repr.batch import bucket_cap

        _log.warn(
            "mv sink self-correction: collection diverged from its "
            "dataflow; healing",
            mv=mv_gid,
            rows=n,
            ts=ts,
        )
        self.mv_corrections = getattr(self, "mv_corrections", 0) + n
        correction = correction.with_capacity(bucket_cap(n))
        self.storage[mv_gid].append(correction, ts)
        return correction

    def _persist_batches(
        self,
        batches: dict[str, UpdateBatch],
        ts: int,
        atomic: bool = False,
        extra_shards: dict | None = None,
    ) -> None:
        from ..persist import Fenced

        try:
            all_cols = {gid: _batch_to_cols(b) for gid, b in batches.items()}
            all_cols.update(extra_shards or {})
            if atomic and len(all_cols) > 1:
                # multi-shard statement: one txn-wal commit is the
                # all-or-nothing point (persist/txn.py)
                self._txn_machine().commit(all_cols, ts, epoch=self.epoch)
                return
            for gid, cols in all_cols.items():
                m = self._shard(gid)
                lower = m.upper()
                m.compare_and_append(cols, lower, ts + 1, epoch=self.epoch)
        except Fenced:
            self.deploy_state = "fenced"
            raise

    def _txn_machine(self):
        from ..persist import TxnsMachine

        tx = getattr(self, "_txns", None)
        if tx is None:
            tx = self._txns = TxnsMachine(self.blob, self.consensus)
            tx._machines = self.shards  # share ShardMachine handles
        return tx

    def _drive_compaction(self, ts: int) -> None:
        """Advance `since` on dataflow state and storage arrangements, keeping
        a configured window of history and honoring subscription read holds
        (the reference's read-policy + AllowCompaction loop,
        coord/read_policy.rs)."""
        window = int(self.configs.get("compaction_window"))
        if window <= 0:
            return
        since = ts - window
        # subscription read holds live on the CHANNELS (one hold per
        # collection × columns, advanced once per tick, seeded at subscribe
        # time — every coordinator-created subscription carries a channel),
        # so this scan is O(channels + sinks), never O(subscribers)
        for ch in self.fanout.live():
            since = min(since, ch.frontier - 1)
        for sink in self.sinks.values():
            # sink read hold: commit-first re-derivation needs source shard
            # history back to the last committed frame's frontier
            since = min(since, sink.frontier - 1)
        if since <= 0:
            return
        for _gid, df, _src in self.dataflows:
            df.compact(since)
        for gid, store in self.storage.items():
            if hasattr(store, "arr"):
                store.arr.compact(since)
        # persist maintenance: strided so the CAS/gc cost amortizes across
        # ticks (the reference runs these as background maintenance tasks,
        # src/persist-client/src/internal/maintenance.rs)
        if self.durable and ts % 16 == 0:
            for _gid, m in list(self.shards.items()):
                try:
                    m.downgrade_since(since)
                    if ts % 64 == 0:
                        m.compact()
                        m.gc()
                except (IOError, RuntimeError):
                    pass  # best-effort; the next maintenance pass retries
            if ts % 64 == 0:
                try:
                    tm = self._txn_machine()
                    tm.forget_applied()  # retire applied commits first,
                    tm.gc()  # then sweep the now-unreferenced payloads
                except (IOError, RuntimeError):
                    pass

    def advance(self, n_rows: int = 100) -> int:
        """Pull one batch from every generator source and commit it.

        Ingest is byte-budgeted (`source_ingest_budget_bytes`): each source
        gets a bounded grant per tick and YIELDS its remainder to later ticks
        instead of growing this tick without bound — the backpressure half of
        overload protection (storage/backpressure.py). Yields are counted in
        mz_overload_counters.ingest_yields."""
        from ..storage.backpressure import IngestBudget, batch_bytes_estimate

        ts = self.oracle.write_ts()
        writes: dict[str, UpdateBatch] = {}
        budget = IngestBudget(int(self.configs.get("source_ingest_budget_bytes")))
        for gen, gids in self.generators:
            # a spent budget still grants one record per source (the
            # IngestBudget liveness floor): sources shrink, never starve
            if isinstance(gen, AuctionGenerator):
                batches = gen.next_tick(ts, budget.grant_rows(gen.ROW_BYTES, n_rows))
            elif isinstance(gen, CounterGenerator):
                budget.grant_rows(gen.ROW_BYTES, 1)
                batches = gen.next_tick(ts, 1)
            elif hasattr(gen, "upsert"):  # KeyValueGenerator
                batches = gen.next_tick(ts, budget.grant_rows(gen.ROW_BYTES, n_rows))
            else:
                # TPC-H refresh sizes itself; charge the actual batches so
                # later sources in the same tick see the spend
                batches = gen.refresh(ts)
                for b in batches.values():
                    budget.charge(batch_bytes_estimate(b))
            for t, b in batches.items():
                if t in gids:
                    writes[gids[t]] = b
                    self._note_source_progress(
                        gids[t],
                        records=int(b.count()),
                        nbytes=batch_bytes_estimate(b),
                    )
        remap, committed = self._poll_file_sources(writes, ts, n_rows, budget)
        if budget.yields:
            self.overload.bump("ingest_yields", budget.yields)
        # remap alone (all polled lines blank/malformed) still commits: the
        # binding must advance src.offset or the same bytes are re-read and
        # re-counted in decode_errors every tick (advisor r2, low)
        if not writes and not remap:
            # a quiet tick must still advance the dataflow frontiers: the
            # oracle's write_ts above already moved read_ts forward, and an
            # MV peek at read_ts >= frontier errors as incomplete — a tick
            # that ingests nothing would wedge every MV read until the next
            # real write (crash-matrix finding). Leaders only: a preflight/
            # fenced instance must not trip the read-only write guard.
            if self.deploy_state == "leader":
                self._apply_writes({}, ts)
            return ts
        durable_point_passed = False

        def _advance_sources():
            nonlocal durable_point_passed
            durable_point_passed = True
            for src, new_offset, _backup in committed:
                src.offset = new_offset

        try:
            self._apply_writes(
                writes, ts, extra_shards=remap, on_durable=_advance_sources
            )
        except Exception:
            if not durable_point_passed:
                # nothing was committed: roll the pollers back so the
                # records are re-polled next tick (offsets/upsert state
                # must never run ahead of the durable remap binding)
                for src, _new_offset, backup in committed:
                    if backup is not None:
                        backup[0].state = backup[1]
            raise
        return ts

    # -- compute replicas ------------------------------------------------------
    def create_compute_replica(
        self, name: str, size: str, orchestrator=None, epoch: int = 1,
        cpu: bool = True, heartbeat_interval: float | None = None,
    ):
        """Allocate a compute replica of `size` ("PxW": processes × workers)
        as real clusterd subprocesses reading this coordinator's persist
        location, and return its controller (ShardedComputeController for
        multi-worker sizes, ComputeController for "1"/"1x1").

        The adapter-side half of CREATE CLUSTER REPLICA ... SIZE: the
        coordinator owns the durable state (blob/consensus), the epoch, AND
        the replica's process lifecycle — drop it with
        `drop_compute_replica(name)` (a coordinator-owned orchestrator would
        otherwise leak the clusterd processes). `cpu=True` pins the replica
        processes to the CPU backend (tests/dev; pass cpu=False to let the
        replicas claim the TPU plane). Requires a durable coordinator
        (data_dir / FileBlob-backed) — clusterd hydrates from shards, never
        from this process.
        """
        from ..cluster import ComputeController, ShardedComputeController
        from ..orchestrator import ProcessOrchestrator

        if not self.durable or not hasattr(self.blob, "root"):
            raise RuntimeError(
                "compute replicas need a file-backed coordinator (data_dir=...)"
            )
        if name in self._compute_replicas:
            raise RuntimeError(f"compute replica {name!r} already exists")
        processes, workers = parse_replica_size(size)
        owned = orchestrator is None
        if owned:
            orchestrator = ProcessOrchestrator(cpu=cpu)
        # ship the dyncfg snapshot (frame cap, exchange deadline) and wire
        # the self-healing loop: heartbeats detect a dead/amnesiac shard, the
        # orchestrator restart hook brings the process back, and the
        # controller reforms at a bumped epoch — no coordinator intervention
        config = self.configs.snapshot()
        if processes == 1 and workers == 1:
            addrs = orchestrator.ensure_service(name, scale=1)
            ctl = ComputeController(
                addrs, self.blob.root, self.consensus.root, epoch=epoch,
                config=config, heartbeat_interval=heartbeat_interval,
            )
        else:
            addrs, mesh_addrs = orchestrator.ensure_sharded_service(
                name, processes, workers_per_process=workers
            )
            ctl = ShardedComputeController(
                addrs,
                mesh_addrs,
                workers,
                self.blob.root,
                self.consensus.root,
                epoch=epoch,
                config=config,
                heartbeat_interval=heartbeat_interval,
                restart_shard=orchestrator.restarter(name)
                if hasattr(orchestrator, "restarter")
                else None,
            )
        self._compute_replicas[name] = (ctl, orchestrator, owned)
        return ctl

    def drop_compute_replica(self, name: str) -> None:
        """Tear down a replica created here: close the controller and stop
        its clusterd processes (only if this coordinator spawned them)."""
        ctl, orchestrator, owned = self._compute_replicas.pop(name)
        ctl.close()
        if owned:
            orchestrator.drop_service(name)

    def replica_peek(self, dataflow_id: str, index_id: str, at=None):
        """Serve a peek from ANY live compute replica (absorb_peek_response:
        replicas are interchangeable). Graceful degradation: a replica that
        is mid-reform (degraded) or errors is skipped, so one sharded
        replica's recovery never blocks reads that another replica — or the
        same replica a moment later — can answer."""
        if not self._compute_replicas:
            raise RuntimeError("no compute replicas")
        last: Exception | None = None
        for name, (ctl, _orch, _owned) in self._compute_replicas.items():
            if getattr(ctl, "degraded", False):
                last = RuntimeError(f"replica {name!r} degraded (reforming)")
                continue
            try:
                return ctl.peek(dataflow_id, index_id, at=at)
            except (ConnectionError, OSError, RuntimeError) as e:
                last = e
        raise RuntimeError(f"no replica could serve peek {index_id}: {last}")

    def replica_stats(self) -> list:
        """[(replica_name, StatsReport)] merged from every live replica's
        FetchStats — the coordinator-side half of the partitioned-peek-style
        introspection merge (the per-process halves are summed in clusterd).

        Cached for `introspection_interval_s` so a burst of introspection
        peeks or /metrics scrapes costs one CTP round-trip, and fail-soft:
        a degraded or unreachable replica drops out of the snapshot instead
        of failing the read."""
        interval = float(self.configs.get("introspection_interval_s"))
        cache = getattr(self, "_introspection_cache", None)
        now = _monotonic()
        if cache is not None and interval > 0 and now - cache[0] < interval:
            return cache[1]
        reports: list = []
        for name, (ctl, _orch, _owned) in self._compute_replicas.items():
            if getattr(ctl, "degraded", False):
                continue
            try:
                for rep in ctl.fetch_stats():
                    reports.append((name, rep))
            except (ConnectionError, OSError, RuntimeError):
                continue
        self._introspection_cache = (now, reports)
        return reports

    def _note_source_progress(
        self, gid: str, records: int = 0, nbytes: int = 0, offset=None
    ) -> None:
        st = self.source_stats.setdefault(
            gid, {"offset": 0, "bytes": 0, "records": 0, "updated": 0.0}
        )
        st["records"] += int(records)
        st["bytes"] += int(nbytes)
        if offset is not None:
            st["offset"] = int(offset)
        st["updated"] = _time.time()

    # -- external file sources -------------------------------------------------
    def _poll_file_sources(self, writes: dict, ts: int, max_records: int,
                           budget=None):
        """Ingest new records from every file source into `writes`; returns
        the remap-shard bindings to commit atomically with the data
        (reclocking: offset ranges bind to engine timestamps exactly once,
        reference src/storage/src/source/reclock.rs:277). `budget` is the
        tick's shared IngestBudget: polls are byte-capped and unread bytes
        wait for a later tick (the remap binding only ever covers what was
        actually consumed, so exactly-once is unaffected)."""
        remap: dict[str, dict] = {}
        committed: list = []  # (src, new_offset, (upsert_state, backup)|None)
        for entry in getattr(self, "file_sources", []):
            src, gid, upsert_state = entry
            item = next(
                (
                    it
                    for it in self.catalog.items.values()
                    if it.global_id == gid
                ),
                None,
            )
            if item is None:
                continue  # dropped concurrently
            max_bytes = budget.remaining if budget is not None else None
            if max_bytes is not None and max_bytes <= 0:
                # liveness floor: a spent budget still reads ONE record (the
                # capped poll extends to its line's end), so an earlier
                # hungry source can never starve this one tick after tick
                budget.note_yield()
                max_bytes = 1
            try:
                records, new_offset = src.poll(max_records, max_bytes=max_bytes)
            except OSError:
                continue  # transient file trouble; retry next tick
            if budget is not None:
                budget.charge(new_offset - src.offset)
                if max_bytes is not None:
                    import os as _os

                    try:
                        size = _os.path.getsize(src.spec.path)
                    except OSError:
                        size = new_offset
                    # a binding cap (smaller than what was pending) with
                    # bytes left over = this source yielded to later ticks
                    if size - src.offset > max_bytes and size > new_offset:
                        budget.note_yield()
            if new_offset == src.offset:
                continue
            self._note_source_progress(
                gid,
                records=len(records),
                nbytes=new_offset - src.offset,
                offset=new_offset,
            )
            backup = None
            if upsert_state is not None:
                backup = (upsert_state, dict(upsert_state.state))
            batch = self._decode_file_records(records, item.desc, src, upsert_state, ts)
            if batch is not None:
                writes[gid] = (
                    batch
                    if gid not in writes
                    else UpdateBatch.concat(writes[gid], batch)
                )
            remap[f"{gid}_remap"] = {
                "c0": np.array([new_offset], dtype=np.int64),
                "times": np.full(1, ts, dtype=np.uint64),
                "diffs": np.ones(1, dtype=np.int64),
            }
            committed.append((src, new_offset, backup))
        return remap or None, committed

    def _decode_file_records(self, records, desc, src, upsert_state, ts):
        """Typed columns from decoded record dicts (the interchange layer)."""
        if not records:
            return None
        spec = src.spec
        names = [c.name for c in desc.columns]
        if spec.envelope == "upsert":
            key_idx = [names.index(k) for k in spec.key_cols]
            val_idx = [i for i in range(len(names)) if i not in key_idx]
            keys, values = [], []
            for r in records:
                k = tuple(
                    self._coerce_source_value(r.get(names[i]), desc.columns[i])
                    for i in key_idx
                )
                vals_present = any(r.get(names[i]) is not None for i in val_idx)
                if not vals_present:
                    values.append(None)  # tombstone
                else:
                    values.append(
                        tuple(
                            self._coerce_source_value(r.get(names[i]), desc.columns[i])
                            for i in val_idx
                        )
                    )
                keys.append(k)
            # upsert emits rows as (key cols ++ val cols); reorder to desc order
            out = upsert_state.apply(
                keys, values, ts, len(val_idx),
                tuple(desc.columns[i].dtype for i in key_idx),
                tuple(desc.columns[i].dtype for i in val_idx),
            )
            order = key_idx + val_idx
            inv = [order.index(i) for i in range(len(names))]
            return UpdateBatch(
                out.hashes, out.keys,
                tuple(out.vals[i] for i in inv),
                out.times, out.diffs,
            )
        rows, diffs = [], []
        for r in records:
            d = int(r.get("__diff__", 1))
            rows.append(
                tuple(
                    self._coerce_source_value(r.get(n), cd)
                    for n, cd in zip(names, desc.columns)
                )
            )
            diffs.append(d)
        cols = tuple(
            np.array([row[i] for row in rows], dtype=desc.columns[i].dtype)
            for i in range(len(names))
        )
        return UpdateBatch.build(
            (), cols, np.full(len(rows), ts, dtype=np.uint64),
            np.array(diffs, dtype=np.int64),
        )

    def _coerce_source_value(self, v, cdesc: ColumnDesc):
        from ..expr.scalar import null_sentinel

        if v is None:
            return null_sentinel(cdesc.dtype)
        if cdesc.typ == ColType.STRING:
            return self.catalog.dict.encode(str(v))
        if cdesc.typ == ColType.JSONB:
            import json as _json

            # sources deliver either parsed JSON (json format) or text
            text = v if isinstance(v, str) else _json.dumps(v)
            return self.catalog.dict.encode(self._json_canonical(text))
        if cdesc.typ == ColType.BOOL:
            if isinstance(v, str):
                return 1 if v.lower() in ("t", "true", "1") else 0
            return 1 if v else 0
        if cdesc.typ == ColType.NUMERIC:
            from decimal import Decimal

            return int(Decimal(str(v)).scaleb(cdesc.scale))
        if cdesc.typ == ColType.FLOAT64:
            return float(v)
        if isinstance(v, str) and len(v) == 10 and v[4] == "-" and v[7] == "-":
            from ..storage.generator import date_num

            y, m, d = (int(x) for x in v.split("-"))
            return int(date_num(y, m, d))
        return int(v)

    # -- reads -----------------------------------------------------------------
    def _result_budget(self) -> int | None:
        """max_result_size in bytes, or None when unlimited (0)."""
        b = int(self._cfg().get("max_result_size"))
        return b if b > 0 else None

    def _select(self, query: ast.Query) -> ExecResult:
        import time as _time

        from ..utils.tracing import TRACER

        t0 = _time.perf_counter_ns()
        self.check_cancellation()
        with TRACER.span("plan"):
            pq = self.planner.plan_query(query)
            rel = optimize(pq.mir, self._cfg())
        as_of = self.oracle.read_ts()

        with TRACER.span("peek"):
            rows = self._peek_fast_path(rel, as_of)
        if rows is None:
            with TRACER.span("peek:slow_path"):
                self.slow_path_peeks = getattr(self, "slow_path_peeks", 0) + 1
                src_gids = sorted(_collect_gets(rel))
                env = {g: self.storage[g].dtypes for g in src_gids}
                desc = lower_to_dataflow(
                    "peek", rel, env, src_gids, as_of=as_of, mono_ids=self._mono_ids(),
                    until=as_of + 1,
                )
                # ephemeral peeks IMPORT shared traces (export=False: a trace
                # exported by a one-tick dataflow would instantly go stale) and
                # hold them at as_of for the peek's lifetime; get_arrangement
                # validates as_of against each shared since — a trace compacted
                # past as_of is skipped so the peek renders privately from
                # snapshots instead of reading a partial history
                tm = self._traces()
                peek_reader = None
                if tm is not None:
                    self._peek_seq = getattr(self, "_peek_seq", 0) + 1
                    peek_reader = f"_peek_{self._peek_seq}"
                try:
                    df = Dataflow(
                        desc, traces=tm, trace_reader=peek_reader, trace_export=False
                    )
                    # the ephemeral dataflow is cancel-safe: no shared state to
                    # tear, so the tick loop checks the deadline between every
                    # dispatch
                    df.cancel_check = self.check_cancellation
                    snaps = {g: self.storage[g].snapshot(as_of) for g in src_gids}
                    df.step(as_of, snaps)
                    rows = df.peek("idx_peek", byte_budget=self._result_budget())
                finally:
                    if tm is not None:
                        # the peek expiring releases its holds (compaction re-arms)
                        tm.release(peek_reader)
        rows = self._finish(rows, pq)
        self._record_peek(_time.perf_counter_ns() - t0)
        return ExecResult("rows", rows=rows, columns=tuple(c.name for c in pq.scope.cols))

    # power-of-two histogram of peek durations (mz_peek_durations analogue)
    def _record_peek(self, ns: int) -> None:
        if not hasattr(self, "peek_histogram"):
            self.peek_histogram: dict[int, int] = {}
        bucket = 1
        while bucket < ns:
            bucket <<= 1
        self.peek_histogram[bucket] = self.peek_histogram.get(bucket, 0) + 1

    def _peek_fast_path(self, rel, as_of: int):
        """Fast-path peeks (peek.rs:119 path (a)): a Get of a maintained
        collection, optionally under a Map/Filter/Project chain — the chain is
        applied host-side to the peeked rows (FastPathPlan::PeekExisting with
        an MFP), avoiding an ephemeral dataflow build entirely."""
        if not bool(self.configs.get("enable_index_fast_path")):
            return None
        # peel a Map/Filter/Project chain down to a Get
        chain = []
        base = rel
        while isinstance(base, (mir.MirMap, mir.MirFilter, mir.MirProject)):
            chain.append(base)
            base = base.input
        if chain and isinstance(base, mir.MirGet):
            inner_rows = self._peek_fast_path(base, as_of)
            if inner_rows is None:
                return None
            from ..expr.linear import MfpBuilder

            b = MfpBuilder(mir.arity(base))
            for node in reversed(chain):
                if isinstance(node, mir.MirMap):
                    b.add_maps(node.exprs)
                elif isinstance(node, mir.MirFilter):
                    b.add_predicates(node.predicates)
                else:
                    b.project(node.outputs)
            mfp = b.finish()
            out = []
            for _i, row in enumerate(inner_rows):
                if (_i & 1023) == 0:
                    self.check_cancellation()
                cols = list(row)
                err = None
                for m in mfp.map_exprs:
                    try:
                        cols.append(_eval_scalar_on_row(m, cols))
                    except Exception as e:
                        cols.append(None)
                        err = err or e
                keep = True
                for p in mfp.predicates:
                    try:
                        ok = bool(_eval_scalar_on_row(p, cols))
                    except Exception as e:
                        err = err or e
                        ok = True  # an erroring predicate errors, not filters
                    keep = keep and ok
                if not keep:
                    continue  # guard semantics: filtered rows cannot error
                if err is not None:
                    raise RuntimeError(f"query error: {err}")
                out.append(tuple(cols[i] for i in mfp.projection))
            return sorted(out, key=_null_safe_row_key)
        if isinstance(rel, mir.MirGet):
            budget = self._result_budget()
            for mv_gid, df, _src in self.dataflows:
                if mv_gid == rel.id:
                    rows = df.peek(f"idx_{mv_gid}", at=as_of, byte_budget=budget)
                    return self._sentinels_to_none(rows, rel.id)
            st = self.storage.get(rel.id)
            if st is not None:
                out: dict = {}
                if hasattr(st, "arr"):  # host path: no XLA for plain scans
                    triples = st.arr.rows_host(as_of)
                else:  # introspection collections build a fresh batch
                    triples = st.snapshot(as_of).to_rows()
                for _i, (data, _t, d) in enumerate(triples):
                    if (_i & 4095) == 0:
                        self.check_cancellation()
                    out[data] = out.get(data, 0) + d
                from ..dataflow.runtime import materialize_counts

                return self._sentinels_to_none(
                    materialize_counts(out, rel.id, byte_budget=budget), rel.id
                )
        return None

    def _sentinels_to_none(self, rows: list, gid: str) -> list:
        """Encoded host rows → None-space NULLs, by storage column dtype.

        Host-side expression evaluation (fast-path MFPs, UPDATE assignments)
        cannot tell a -128 INT64 from a NULL BOOL by value alone; the storage
        dtype disambiguates. Idempotent for rows already holding None."""
        st = self.storage.get(gid)
        if st is None:
            return rows
        import numpy as _np

        from ..expr.scalar import NULL_I8, NULL_I32, NULL_I64

        sentinels = []
        for dt in st.dtypes:
            dt = _np.dtype(dt)
            if dt == _np.int8:
                sentinels.append(int(NULL_I8))
            elif dt == _np.int32:
                sentinels.append(int(NULL_I32))
            elif dt in (_np.dtype(_np.int64), _np.dtype(_np.uint64)):
                sentinels.append(int(NULL_I64))
            else:
                sentinels.append(None)  # floats: NaN checked directly
        out = []
        for r in rows:
            out.append(
                tuple(
                    None
                    if v is None
                    or (isinstance(v, float) and v != v)
                    or (sentinels[i] is not None and int(v) == sentinels[i])
                    else v
                    for i, v in enumerate(r)
                )
            )
        return out

    def _finish(self, rows: list, pq: PlannedQuery) -> list:
        from ..dataflow.runtime import row_bytes_estimate
        from ..errors import ResultSizeExceeded

        f = pq.finishing
        # max_result_size bounds the MATERIALIZED working set (pre-LIMIT:
        # ORDER BY needs every row in memory before the limit can apply), so
        # the decode loop stops at the budget instead of building the rest
        budget = self._result_budget()
        decoded = []
        spent = 0
        for i, r in enumerate(rows):
            if (i & 511) == 0:
                self.check_cancellation()
            d = self._decode_row(r, pq)
            if budget is not None:
                spent += row_bytes_estimate(d)
                if spent > budget:
                    raise ResultSizeExceeded(
                        f"result exceeds max_result_size ({budget} bytes); "
                        f"aborted after {len(decoded)} rows"
                    )
            decoded.append(d)
        if f.order_by:
            nulls = f.nulls_last or tuple(not d for _c, d in f.order_by)
            for (col, desc_), nl in reversed(list(zip(f.order_by, nulls))):
                # k0 places NULLs per the requested side under the reverse
                # flag (pg default: NULLS LAST ascending, FIRST descending)
                null_hi = nl != desc_
                decoded.sort(
                    key=lambda r: (
                        (r[col] is None) if null_hi else (r[col] is not None),
                        r[col] if r[col] is not None else 0,
                    ),
                    reverse=desc_,
                )
        if f.offset:
            decoded = decoded[f.offset :]
        if f.limit is not None:
            decoded = decoded[: f.limit]
        return decoded

    def _decode_row(self, row: tuple, pq: PlannedQuery) -> tuple:
        from ..expr.scalar import is_null_value

        out = []
        for v, c in zip(row, pq.scope.cols):
            t = c.typ
            if is_null_value(v, t.col):
                out.append(None)
            elif t.col in (ColType.STRING, ColType.JSONB):
                out.append(self.catalog.dict.decode(int(v)))
            elif t.col == ColType.NUMERIC and t.scale:
                out.append(v / (10**t.scale))
            elif t.col == ColType.BOOL:
                out.append(bool(v))
            else:
                out.append(v)
        return tuple(out)

    # -- introspection ---------------------------------------------------------
    def _explain(self, stmt: ast.Explain) -> ExecResult:
        inner = stmt.statement
        if stmt.stage == "timeline":
            # run the inner statement under a fresh trace, then render the
            # end-to-end span tree — including clusterd-side spans absorbed
            # from TracedResponses (obs/spans.py)
            from ..obs.spans import TRACER, render_timeline

            with TRACER.trace(f"timeline:{type(inner).__name__}") as root:
                # through execute_stmt, not _execute_stmt_inner: the nested
                # call records its "execute:<Stmt>" span as a child here
                self.execute_stmt(inner)
            spans = TRACER.spans_for_trace(root.trace_id)
            return ExecResult(
                "rows",
                rows=[(line,) for line in render_timeline(spans)],
                columns=("timeline",),
            )
        if stmt.stage == "timestamp" and isinstance(inner, ast.SelectStatement):
            pq = self.planner.plan_query(inner.query)
            rel = optimize(pq.mir, self._cfg())
            as_of = self.oracle.read_ts()
            lines = [f"query timestamp: {as_of}", f"oracle read:     {as_of}"]
            for gid in sorted(_collect_gets(rel)):
                name = next(
                    (i.name for i in self.catalog.items.values() if i.global_id == gid),
                    gid,
                )
                st = self.storage.get(gid)
                upper = getattr(st, "upper", "?")
                since = getattr(getattr(st, "arr", None), "since", 0)
                lines.append(f"source {name} ({gid}): [{since}, {upper})")
            return ExecResult(
                "rows", rows=[(line,) for line in lines], columns=("timestamp",)
            )
        if isinstance(inner, ast.SelectStatement):
            pq = self.planner.plan_query(inner.query)
            rel = (
                optimize(pq.mir, self.configs)
                if stmt.stage in ("optimized", "physical")
                else pq.mir
            )
            if stmt.stage == "physical":
                src_gids = sorted(_collect_gets(rel))
                env = {g: self.storage[g].dtypes for g in src_gids}
                lo = Lowerer(env, self._mono_ids())
                text = explain_lir(lo.lower(rel))
            else:
                text = explain_mir(rel)
            return ExecResult("rows", rows=[(line,) for line in text.splitlines()], columns=("plan",))
        raise PlanError("EXPLAIN supports SELECT only")

    def _show(self, stmt: ast.Show) -> ExecResult:
        kind_map = {
            "tables": ("table",),
            "views": ("view",),
            "sources": ("source",),
            "indexes": ("index",),
            "materialized": ("materialized_view",),
        }
        if stmt.what == "all":
            cfg = self._cfg()
            rows = [(name, str(cfg.get(name))) for name in self.configs.names()]
            return ExecResult("rows", rows=rows, columns=("name", "setting"))
        kinds = kind_map.get(stmt.what)
        if kinds is None and stmt.what in self.configs.names():
            return ExecResult(
                "rows", rows=[(str(self._cfg().get(stmt.what)),)], columns=(stmt.what,)
            )
        if kinds is None:
            if stmt.what == "columns" and stmt.on:
                item = self.catalog.get(stmt.on)
                rows = [(c.name, c.typ.value) for c in item.desc.columns]
                return ExecResult("rows", rows=rows, columns=("name", "type"))
            raise PlanError(f"SHOW {stmt.what} unsupported")
        rows = [(i.name,) for i in self.catalog.items.values() if i.kind in kinds]
        return ExecResult("rows", rows=sorted(rows), columns=("name",))


def explain_lir(e, indent: int = 0) -> str:
    """EXPLAIN PHYSICAL PLAN rendering of a lowered LIR tree."""
    pad = "  " * indent
    name = type(e).__name__
    extra = ""
    kids = []
    if isinstance(e, lir.Get):
        extra = f" {e.id}"
    elif isinstance(e, lir.Mfp):
        m = e.mfp
        extra = f" maps={len(m.map_exprs)} preds={len(m.predicates)}"
        kids = [e.input]
    elif isinstance(e, lir.Join):
        kind = "delta" if isinstance(e.plan, lir.DeltaJoinPlan) else "linear"
        extra = f" type={kind}"
        kids = list(e.inputs)
    elif isinstance(e, lir.Reduce):
        extra = f" keys={list(e.key_cols)} aggs={[a.func for a in e.aggs]}" + (
            " distinct" if e.distinct else ""
        )
        kids = [e.input]
    elif isinstance(e, lir.TopK):
        extra = f" group={list(e.plan.group_cols)} limit={e.plan.limit}" + (
            " monotonic" if getattr(e, "monotonic", False) else ""
        )
        kids = [e.input]
    elif isinstance(e, lir.BasicAgg):
        extra = f" keys={list(e.key_cols)} func={e.func}"
        kids = [e.input]
    elif isinstance(e, (lir.Negate, lir.Threshold, lir.ArrangeBy, lir.TemporalFilter)):
        kids = [e.input]
    elif isinstance(e, lir.Union):
        kids = list(e.inputs)
    elif isinstance(e, lir.LetRec):
        extra = f" bindings={len(e.bindings)}"
        kids = [b[1] for b in e.bindings] + [e.body]
    elif isinstance(e, lir.Constant):
        extra = f" rows={len(e.rows)}"
    lines = [f"{pad}{name}{extra}"]
    for k in kids:
        lines.append(explain_lir(k, indent + 1))
    return "\n".join(lines)


def _null_safe_row_key(row: tuple):
    """Deterministic sort key for host-path rows that may hold None."""
    return tuple((v is None, 0 if v is None else v) for v in row)


def _eval_scalar_on_row(e, row: list):
    """Host interpreter for a planned ScalarExpr over one encoded row
    (UPDATE assignments, fast-path peek MFPs; mirrors eval_expr3's
    three-valued semantics with Python None as NULL)."""
    from ..expr import scalar as s
    from ..expr.scalar import is_null_value

    if isinstance(e, s.Column):
        v = row[e.index]
        return None if is_null_value(v) else v
    if isinstance(e, s.Literal):
        return e.value
    if isinstance(e, s.CallUnary):
        v = _eval_scalar_on_row(e.expr, row)
        if e.func == "is_null":
            return v is None
        if e.func == "is_not_null":
            return v is not None
        if v is None:
            return None
        if e.func in ("extract_year", "extract_month", "extract_day"):
            from ..expr.scalar import civil_from_days_int

            y, m, d = civil_from_days_int(int(v))
            return {"extract_year": y, "extract_month": m, "extract_day": d}[e.func]
        if e.func == "sqrt":
            # f32 like the device kernel (expr/scalar.py sqrt), so host
            # fast-path peeks agree bit-for-bit with rendered dataflows
            return float(np.sqrt(np.float32(v), dtype=np.float32))
        if e.func in s._DATE_UNARY:
            from ..expr.scalar import date_unary_int

            return date_unary_int(e.func, int(v))
        if e.func in s._FLOAT_UNARY_NP:
            return float(np.float32(s._FLOAT_UNARY_NP[e.func](np.float32(v))))
        if e.func == "round_half_away":
            fv = np.float32(v)
            return float(np.float32(np.sign(fv) * np.floor(np.abs(fv) + np.float32(0.5))))
        if e.func == "sign":
            return float(np.sign(v)) if isinstance(v, float) else int(np.sign(v))
        return {
            "neg": lambda: -v,
            "not": lambda: not v,
            "abs": lambda: abs(v),
            "cast_int64": lambda: int(v),
            "cast_int32": lambda: int(v),
            "cast_float": lambda: float(np.float32(v)),
            "is_true": lambda: bool(v),
        }[e.func]()
    if isinstance(e, s.CallBinary):
        l = _eval_scalar_on_row(e.left, row)
        r = _eval_scalar_on_row(e.right, row)
        if e.func == "and":  # Kleene: FALSE dominates NULL
            if l is False or r is False or l == 0 and l is not None or r == 0 and r is not None:
                return False
            if l is None or r is None:
                return None
            return bool(l) and bool(r)
        if e.func == "or":  # Kleene: TRUE dominates NULL
            if (l is not None and bool(l)) or (r is not None and bool(r)):
                return True
            if l is None or r is None:
                return None
            return False
        if l is None or r is None:
            return None
        # float arithmetic mirrors the device's f32 kernels exactly, so a
        # fast-path peek and a rendered dataflow never disagree on a value
        # (the FLOAT64 precision rule, repr/types.py)
        fl = isinstance(l, float) or isinstance(r, float)

        def f32(x):
            return float(np.float32(x))

        if e.func in ("div", "floordiv"):
            if r == 0:
                raise PlanError("division by zero")
            if fl:
                return f32(np.float32(l) / np.float32(r))
            q = abs(l) // abs(r)
            return -q if (l < 0) != (r < 0) else q
        if e.func in ("fdiv", "fmod"):
            if r == 0:
                raise PlanError("division by zero")
            return l // r if e.func == "fdiv" else l - r * (l // r)
        if e.func == "mul_exact":
            if abs(l * r) >= 1 << 63:
                raise PlanError("numeric overflow")
            return l * r
        if e.func == "add_months":
            from ..expr.scalar import add_months_int

            return add_months_int(int(l), int(r))
        return {
            "add": lambda: f32(np.float32(l) + np.float32(r)) if fl else l + r,
            "sub": lambda: f32(np.float32(l) - np.float32(r)) if fl else l - r,
            "mul": lambda: f32(np.float32(l) * np.float32(r)) if fl else l * r,
            # float mod mirrors the device's f32 kernel step-for-step
            # (advisor r4: f64 host arithmetic could disagree with a
            # rendered dataflow for float operands)
            "mod": lambda: (
                f32(
                    np.float32(l)
                    - np.float32(r)
                    * np.float32(
                        (np.abs(np.float32(l)) // np.abs(np.float32(r)))
                        * (1 if (l < 0) == (r < 0) else -1)
                    )
                )
                if fl
                else l - r * (abs(l) // abs(r)) * (1 if (l < 0) == (r < 0) else -1)
            ),
            "pow": lambda: f32(np.power(np.float32(l), np.float32(r))),
            "atan2": lambda: f32(np.arctan2(np.float32(l), np.float32(r))),
            "eq": lambda: l == r,
            "ne": lambda: l != r,
            "lt": lambda: l < r,
            "lte": lambda: l <= r,
            "gt": lambda: l > r,
            "gte": lambda: l >= r,
            "min": lambda: min(l, r),
            "max": lambda: max(l, r),
        }[e.func]()
    if isinstance(e, s.CallVariadic):
        vs = [_eval_scalar_on_row(x, row) for x in e.exprs]
        if e.func == "if":
            return vs[1] if (vs[0] is not None and vs[0]) else vs[2]
        if e.func == "and":
            if any(v is not None and not v for v in vs):
                return False
            if any(v is None for v in vs):
                return None
            return True
        if e.func == "or":
            if any(v is not None and v for v in vs):
                return True
            if any(v is None for v in vs):
                return None
            return False
        if e.func == "coalesce":
            for v in vs:
                if v is not None:
                    return v
            return None
        if e.func == "nullif":
            a, b = vs
            if a is not None and b is not None and a == b:
                return None
            return a
        if e.func == "greatest":
            nn = [v for v in vs if v is not None]
            return max(nn) if nn else None
        if e.func == "least":
            nn = [v for v in vs if v is not None]
            return min(nn) if nn else None
    if isinstance(e, s.DictFunc):
        vs = [_eval_scalar_on_row(a, row) for a in e.args]
        if e.spec[0] == "concat_ws":
            # NULL args are skipped (passed as None); NULL separator → NULL
            if vs[0] is None:
                return None
            args = [
                None if v is None else e.tables._decode_arg(at, v)
                for at, v in zip(e.argtypes, vs)
            ]
            r = e.tables.eval_one(e.spec, args)
            return None if r is None else e.tables.dct.encode(r)
        if any(v is None for v in vs):
            return None
        args = [e.tables._decode_arg(at, v) for at, v in zip(e.argtypes, vs)]
        r = e.tables.eval_one(e.spec, args)
        if r is None:
            return None
        if e.out == "string":
            return e.tables.dct.encode(r)
        if e.out == "bool":
            return bool(r)
        return int(r)
    raise PlanError(f"cannot evaluate {e!r} host-side")


def _collect_gets(e) -> set:
    return mir.collect_get_ids(e)


def explain_mir(e, indent: int = 0) -> str:
    """EXPLAIN text rendering of a MIR tree (reference: EXPLAIN PLAN)."""
    pad = "  " * indent
    name = type(e).__name__.replace("Mir", "")
    extra = ""
    if isinstance(e, mir.MirGet):
        extra = f" {e.id}"
    if isinstance(e, mir.MirJoin) and e.implementation is not None:
        extra = f" type={e.implementation.kind}"
    if isinstance(e, mir.MirReduce):
        extra = f" keys={list(e.group_key)} aggs={[a.func for a in e.aggregates]}"
    if isinstance(e, mir.MirTopK):
        extra = f" group={list(e.group_key)} limit={e.limit}"
    if isinstance(e, mir.MirWindow):
        extra = (
            f" partition={list(e.partition_cols)}"
            f" funcs={[f.func for f in e.funcs]}"
        )
    lines = [f"{pad}{name}{extra}"]
    for k in mir.children(e):
        lines.append(explain_mir(k, indent + 1))
    return "\n".join(lines)
