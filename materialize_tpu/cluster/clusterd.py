"""clusterd — the cluster worker binary.

The analogue of the reference's `clusterd` (src/clusterd/src/bin/clusterd.rs):
a stateless process that listens for a controller connection, renders
dataflows it is told to build (src/compute/src/compute_state.rs:516
handle_compute_command), pulls source data from persist shards (never from
the controller), answers peeks, and reports frontiers. Restart + reconnect is
safe because the controller replays its command history (reconciliation) and
all inputs re-hydrate from shards.

Two execution modes:

* **Whole replica** (default): one Dataflow per installed dataflow holding
  full state — active-active HA across replicas.
* **Shard of a replica** (after FormMesh, requires --mesh-port): this
  process hosts `workers_per_process` worker threads, each rendering the
  same dataflows with a ShardContext over the epoch-fenced WorkerMesh
  (cluster/mesh.py). Source rows are routed by whole-row hash so each worker
  ingests only its partition; exchange pacts inside the rendered dataflow
  re-route by operator keys. Tick-driving commands (CreateDataflow
  hydration, ProcessTo) fan out to all local workers CONCURRENTLY — workers
  block on each other's exchange parts, so serializing them would deadlock.
"""

from __future__ import annotations

import argparse
import os
import queue
import socket
import sys
import threading

import numpy as np

from ..arrangement.trace_manager import TraceManager
from ..dataflow import Dataflow
from ..dataflow.runtime import ShardContext
from ..obs import log as obs_log
from ..obs import metrics as obs_metrics
from ..obs import profiler as obs_profiler
from ..obs.spans import TRACER
from ..persist import FileBlob, FileConsensus, ShardMachine
from ..repr.batch import UpdateBatch
from . import protocol as p
from .mesh import MeshError, WorkerMesh

_log = obs_log.get_logger("clusterd")


class ShardWorker:
    """One worker thread of a sharded replica process.

    Owns its partition's Dataflow instances; executes jobs posted by the
    command handler. Jobs run concurrently across the process's workers (and
    across processes), meeting each other at mesh exchanges.
    """

    def __init__(self, global_index: int, mesh: WorkerMesh, state: "ClusterState"):
        self.global_index = global_index
        self.mesh = mesh
        self.state = state
        self.dataflows: dict[str, dict] = {}
        # per-(worker, shard) shared-trace registry: dataflows rendered on
        # this worker share one arrangement per (collection, key) holding
        # this worker's partition. Created fresh at FormMesh (state.epoch is
        # already the bumped epoch), so reform drops every trace and hold;
        # the controller's command-history replay reinstalls the dataflows,
        # which re-export the traces and re-register every hold.
        self.traces = TraceManager(epoch=state.epoch)
        self.jobs: queue.Queue = queue.Queue()
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _run(self) -> None:
        while True:
            job = self.jobs.get()
            if job is None:
                return
            fn, done, result = job
            try:
                result.append(fn(self))
            except Exception as e:  # surfaced as CommandErr by the handler
                result.append(e)
            done.set()

    def stop(self) -> None:
        self.jobs.put(None)


def _run_on_workers(workers: list, fn):
    """Post `fn(worker)` to every worker, wait for all, return results;
    raises the first exception (after all workers finished or failed)."""
    pending = []
    for w in workers:
        done = threading.Event()
        result: list = []
        w.jobs.put((fn, done, result))
        pending.append((done, result))
    outs = []
    first_err = None
    for done, result in pending:
        done.wait()
        r = result[0]
        if isinstance(r, Exception) and first_err is None:
            first_err = r
        outs.append(r)
    if first_err is not None:
        raise first_err
    return outs


def _partition_source(cols: dict, n_workers: int) -> list:
    """All workers' partitions of a source column dict in ONE hashing pass
    (whole-row hash — deterministic in the VALUES only, so any later
    retraction of a row is ingested by the same worker as its insert)."""
    from ..parallel.netexchange import partition_cols

    if n_workers == 1:
        return [cols]
    return partition_cols(cols, None, n_workers)


class ClusterState:
    def __init__(self) -> None:
        self.blob = None
        self.consensus = None
        self.epoch = -1
        self.config: dict = {}  # dyncfg snapshot from CreateInstance
        # dataflow_id -> dict(df, source_shards, frontier)  (whole-replica mode)
        self.dataflows: dict[str, dict] = {}
        # whole-replica shared-trace registry (sharded mode keeps one per
        # ShardWorker instead: traces hold per-worker partitions)
        self.traces = TraceManager()
        # sharded mode (set by FormMesh)
        self.mesh: WorkerMesh | None = None
        self.workers: list[ShardWorker] = []
        # dataflow_id -> dict(desc, source_shards, as_of, frontier)
        self.sharded_dataflows: dict[str, dict] = {}

    @property
    def sharded(self) -> bool:
        return bool(self.workers)

    def _mesh_epoch(self) -> int:
        """Epoch of the FORMED mesh (-1 when none): lets the controller's
        heartbeat tell a restarted, state-less shard from a healthy one."""
        if self.mesh is not None and self.workers:
            return self.mesh.epoch
        return -1

    def _mesh_naive(self) -> bool:
        """A mesh-capable process with no formed mesh (fresh start or
        restart): it must refuse state-bearing commands — answering them in
        whole-replica mode would silently serve an EMPTY partition."""
        return self.mesh is not None and not self.workers

    # -- command handlers (compute_state.rs:516 analogue) ---------------------
    def handle(self, cmd):
        if isinstance(cmd, p.Hello):
            if cmd.epoch < self.epoch:
                return p.CommandErr(f"fenced: stale epoch {cmd.epoch} < {self.epoch}")
            self.epoch = cmd.epoch
            return p.Pong(self.epoch, self._mesh_epoch())
        if isinstance(cmd, p.Ping):
            return p.Pong(self.epoch, self._mesh_epoch())
        if isinstance(cmd, p.FormMesh):
            return self._form_mesh(cmd)
        if isinstance(cmd, p.CreateInstance):
            self.blob = FileBlob(cmd.blob_path)
            self.consensus = FileConsensus(cmd.consensus_path)
            cfg = self.config = dict(cmd.config or {})
            if "ctp_max_frame_bytes" in cfg:
                p.set_max_frame_bytes(cfg["ctp_max_frame_bytes"])
            TRACER.set_filter(cfg.get("log_filter", "off"))
            # profiler config rides the dyncfg snapshot too: the fused ticks
            # whose device time matters run HERE, not at the coordinator
            obs_profiler.configure(
                bool(cfg.get("enable_jax_profiler", False)),
                str(cfg.get("jax_profiler_dir", "")),
            )
            # exchange backend is read per-render (_create_dataflow), not set
            # globally; sanitize here so an unknown value in an old snapshot
            # degrades to auto instead of failing every later render
            from ..parallel.devicemesh import EXCHANGE_MODES

            if str(cfg.get("exchange_backend", "auto")) not in EXCHANGE_MODES:
                cfg["exchange_backend"] = "auto"
            return p.Frontiers({})
        if isinstance(cmd, p.FetchStats):
            return self._fetch_stats()
        if self._mesh_naive() and isinstance(
            cmd, (p.CreateDataflow, p.ProcessTo, p.AllowCompaction, p.Peek)
        ):
            msg = "MeshError: no formed mesh at this process (restarted?) — reform required"
            if isinstance(cmd, p.Peek):
                return p.PeekResponse(cmd.uuid, None, msg)
            return p.CommandErr(msg)
        if isinstance(cmd, p.CreateDataflow):
            return self._create_dataflow(cmd)
        if isinstance(cmd, p.AllowCompaction):
            if self.sharded:
                st = self.sharded_dataflows.get(cmd.dataflow_id)
                if st is not None:
                    def compact(w, df_id=cmd.dataflow_id, since=cmd.since):
                        wst = w.dataflows.get(df_id)
                        if wst is not None:
                            wst["df"].compact(since)
                    try:
                        _run_on_workers(self.workers, compact)
                    except Exception as e:
                        return p.CommandErr(str(e))
                return p.Frontiers(self._uppers())
            st = self.dataflows.get(cmd.dataflow_id)
            if st is not None:
                st["df"].compact(cmd.since)
            return p.Frontiers(self._uppers())
        if isinstance(cmd, p.ProcessTo):
            return self._process_to(cmd.upper)
        if isinstance(cmd, p.Peek):
            return self._peek(cmd)
        return p.CommandErr(f"unknown command {type(cmd).__name__}")

    # -- sharded mode ---------------------------------------------------------
    def _form_mesh(self, cmd: p.FormMesh):
        """Join (or re-form) the worker mesh at cmd.epoch. All dataflow state
        is dropped: a sharded replica's state partitions are rebuilt together
        by the controller's history replay, so a restarted shard can never
        hold batches from a different epoch than its peers."""
        if cmd.epoch < self.epoch:
            return p.CommandErr(f"fenced: stale epoch {cmd.epoch} < {self.epoch}")
        self.epoch = cmd.epoch
        if self.mesh is None:
            return p.CommandErr("clusterd was started without --mesh-port")
        for w in self.workers:
            w.stop()
        self.workers = []
        self.dataflows.clear()
        self.sharded_dataflows.clear()
        # shared traces die with the dataflows that held them: the replay
        # that rebuilds state at the bumped epoch rebuilds every hold too
        self.traces = TraceManager(epoch=cmd.epoch)
        try:
            self.mesh.form(
                cmd.epoch,
                cmd.process_index,
                cmd.n_processes,
                cmd.workers_per_process,
                list(cmd.peer_mesh_addrs),
                exchange_timeout=getattr(cmd, "exchange_timeout", None),
            )
        except MeshError as e:
            return p.CommandErr(str(e))
        base = cmd.process_index * cmd.workers_per_process
        self.workers = [
            ShardWorker(base + i, self.mesh, self)
            for i in range(cmd.workers_per_process)
        ]
        # observability identity follows the mesh: spans record which shard
        # produced them, log lines carry (shard, epoch), and the per-operator
        # accumulators are epoch-scoped (workers and their Dataflows were
        # just rebuilt, so the counters restart with the new generation)
        TRACER.set_process(f"shard{cmd.process_index}")
        obs_log.set_context(shard=cmd.process_index, epoch=cmd.epoch)
        _log.info(
            "mesh formed",
            n_processes=cmd.n_processes,
            workers=cmd.workers_per_process,
        )
        return p.MeshReady(cmd.epoch, self.mesh.n_workers)

    def _create_dataflow(self, cmd: p.CreateDataflow):
        if self.sharded:
            return self._create_dataflow_sharded(cmd)
        if cmd.dataflow_id in self.dataflows:
            # reconciliation replay: already installed, keep as-is
            return p.Frontiers(self._uppers())
        # the handle's hydration frame (TraceHandle.as_of) keys off desc.as_of
        cmd.desc.as_of = cmd.as_of
        try:
            # whole-replica mode renders through the shared decision point:
            # this process owns every shard of the dataflow, so a device mesh
            # (exchange_backend=device/auto in the dyncfg snapshot) can carry
            # the exchange on-chip. Sharded mode below stays host-rendered —
            # its worker partitions are not key-closed (doc/DEVICE_MESH.md).
            from ..dataflow.fused import FusedCaps
            from ..dataflow.runtime import render_dataflow

            caps = FusedCaps(
                ratio=int(self.config.get("lsm_merge_ratio", FusedCaps().ratio)),
                cap_ratio=int(
                    self.config.get("fused_join_cap_ratio", FusedCaps().cap_ratio)
                ),
            )
            df = render_dataflow(
                cmd.desc,
                fused=bool(self.config.get("enable_fused_render", False)),
                exchange_backend=str(self.config.get("exchange_backend", "auto")),
                caps=caps,
                traces=self.traces,
                trace_reader=cmd.dataflow_id,
                operator_logging=bool(
                    self.config.get("enable_operator_logging", False)
                ),
            )
        except Exception:
            self.traces.rollback_install(cmd.dataflow_id)
            raise
        st = {
            "df": df,
            "source_shards": dict(cmd.source_shards),
            "frontier": cmd.as_of,
            "as_of": cmd.as_of,
        }
        self.dataflows[cmd.dataflow_id] = st
        try:
            # hydrate from shard snapshots at as_of
            snaps = {}
            for gid, shard_id in st["source_shards"].items():
                m = ShardMachine(self.blob, self.consensus, shard_id)
                _seq, state = m.fetch_state()
                if state.batches:
                    at = max(min(cmd.as_of, state.upper - 1), state.since)
                    batches = m.snapshot(at)
                    if batches:
                        snaps[gid] = _cols_to_batch(batches, cmd.as_of)
            if snaps:
                df.step(cmd.as_of, snaps)
        except Exception:
            # a failed install must not leak its trace exports/holds (or a
            # half-installed dataflow) to the next CreateDataflow replay
            self.dataflows.pop(cmd.dataflow_id, None)
            self.traces.rollback_install(cmd.dataflow_id)
            raise
        st["frontier"] = cmd.as_of + 1
        df.frontier = cmd.as_of + 1
        return p.Frontiers(self._uppers())

    def _create_dataflow_sharded(self, cmd: p.CreateDataflow):
        if cmd.dataflow_id in self.sharded_dataflows:
            return p.Frontiers(self._uppers())
        n_workers = self.mesh.n_workers
        # read + partition snapshots ONCE per process; workers index in
        snaps_parts: dict[str, list] = {}  # gid -> [per-batch parts lists]
        for gid, shard_id in cmd.source_shards.items():
            m = ShardMachine(self.blob, self.consensus, shard_id)
            _seq, state = m.fetch_state()
            if state.batches:
                at = max(min(cmd.as_of, state.upper - 1), state.since)
                batches = m.snapshot(at)
                if batches:
                    snaps_parts[gid] = [
                        _partition_source(c, n_workers) for c in batches
                    ]

        cmd.desc.as_of = cmd.as_of

        def create(w: ShardWorker):
            shard_ctx = ShardContext(
                self.mesh, cmd.dataflow_id, w.global_index, n_workers
            )
            df = Dataflow(
                cmd.desc,
                shard=shard_ctx,
                traces=w.traces,
                trace_reader=cmd.dataflow_id,
                operator_logging=bool(
                    self.config.get("enable_operator_logging", False)
                ),
            )
            snaps = {}
            for gid, batch_parts in snaps_parts.items():
                parts = [
                    bp[w.global_index]
                    for bp in batch_parts
                    if bp[w.global_index] is not None
                ]
                if parts:
                    snaps[gid] = _cols_to_batch(parts, cmd.as_of)
            # the hydration tick runs on EVERY worker even with no local
            # snapshot rows: its exchanges are a mesh-wide barrier
            df.step(cmd.as_of, snaps)
            df.frontier = cmd.as_of + 1
            w.dataflows[cmd.dataflow_id] = {"df": df, "frontier": cmd.as_of + 1}
            return None

        try:
            _run_on_workers(self.workers, create)
        except MeshError as e:
            # a MeshError is retryable by reform; the controller keys on the
            # prefix to drive heal+reform instead of surfacing a hard error
            self._rollback_sharded_create(cmd.dataflow_id)
            return p.CommandErr(f"MeshError: sharded create_dataflow: {e}")
        except Exception as e:
            self._rollback_sharded_create(cmd.dataflow_id)
            return p.CommandErr(f"sharded create_dataflow failed: {e}")
        self.sharded_dataflows[cmd.dataflow_id] = {
            "desc": cmd.desc,
            "source_shards": dict(cmd.source_shards),
            "as_of": cmd.as_of,
            "frontier": cmd.as_of + 1,
        }
        return p.Frontiers(self._uppers())

    def _rollback_sharded_create(self, dataflow_id: str) -> None:
        """Scrub a failed sharded install from every worker: the partially
        rendered Dataflows AND any shared-trace exports/holds they
        registered (a leaked export would feed later imports a trace nobody
        steps). Safe from the handler thread — _run_on_workers has already
        joined every worker's job."""
        for w in self.workers:
            w.dataflows.pop(dataflow_id, None)
            w.traces.rollback_install(dataflow_id)

    def _process_to(self, upper: int):
        """Pull new shard data and step dataflows tick by tick (the worker
        loop: server.rs:356 analogue, driven by explicit ProcessTo)."""
        if self.sharded:
            return self._process_to_sharded(upper)
        # collect per-dataflow per-source updates in [frontier, upper) first…
        per_df: dict[str, dict[int, dict[str, list]]] = {}
        for df_id, st in self.dataflows.items():
            lo = st["frontier"]
            if upper <= lo:
                continue
            per_time: dict[int, dict[str, list]] = {}
            for gid, shard_id in st["source_shards"].items():
                m = ShardMachine(self.blob, self.consensus, shard_id)
                batches, _shard_upper = m.listen_from(lo)
                for cols in batches:
                    mask = cols["times"] < np.uint64(upper)
                    if not mask.any():
                        continue
                    sub = {k: v[mask] for k, v in cols.items()}
                    for t in np.unique(sub["times"]):
                        tmask = sub["times"] == t
                        per_time.setdefault(int(t), {}).setdefault(gid, []).append(
                            {k: v[tmask] for k, v in sub.items()}
                        )
            per_df[df_id] = per_time
        # …then step TICK-major across dataflows: shared traces require that
        # no reader advances past tick t before every reader with data at t
        # has stepped it (a df-major sweep would let the first dataflow drive
        # a shared trace to upper while a later reader still reads at lo).
        # A dataflow quiet at t never reads at t, so skipping it is safe.
        for t in sorted({t for pt in per_df.values() for t in pt}):
            for df_id, per_time in per_df.items():
                if t not in per_time:
                    continue
                deltas = {
                    gid: _cols_to_batch(parts, None)
                    for gid, parts in per_time[t].items()
                }
                self.dataflows[df_id]["df"].step(t, deltas)
        for df_id in per_df:
            st = self.dataflows[df_id]
            st["frontier"] = upper
            st["df"].frontier = upper
        return p.Frontiers(self._uppers())

    def _process_to_sharded(self, upper: int):
        """Sharded ProcessTo: every worker steps EVERY tick in [lo, upper) —
        the per-tick exchanges are how peers learn a timestamp is closed, so
        the tick sequence must be identical mesh-wide even where a worker
        (or the whole replica) has no local data for a tick."""
        n_workers = self.mesh.n_workers
        # read + partition the shard listens once per process, for EVERY
        # pending dataflow, before any tick runs
        pending: list[tuple] = []  # (df_id, lo, {gid: [per-batch parts]})
        for df_id, st in self.sharded_dataflows.items():
            lo = st["frontier"]
            if upper <= lo:
                continue
            per_source: dict[str, list] = {}  # gid -> [per-batch parts lists]
            for gid, shard_id in st["source_shards"].items():
                m = ShardMachine(self.blob, self.consensus, shard_id)
                batches, _shard_upper = m.listen_from(lo)
                subs = []
                for cols in batches:
                    mask = cols["times"] < np.uint64(upper)
                    if mask.any():
                        sub = {k: v[mask] for k, v in cols.items()}
                        subs.append(_partition_source(sub, n_workers))
                if subs:
                    per_source[gid] = subs
            pending.append((df_id, lo, per_source))
        if not pending:
            return p.Frontiers(self._uppers())

        def advance(w: ShardWorker):
            with TRACER.span(f"worker{w.global_index}:process_to"):
                return _advance(w)

        def _advance(w: ShardWorker):
            # Tick-major across dataflows (every dataflow still steps EVERY
            # tick in its [lo, upper) — the exchanges are how peers learn a
            # timestamp is closed): shared traces on this worker require no
            # reader to advance past tick t before the others step it. The
            # per-tick dataflow order is the sharded_dataflows insertion
            # order, identical mesh-wide (same command history), so exchange
            # barriers line up across workers.
            plans = []
            for df_id, lo, per_source in pending:
                per_time: dict[int, dict[str, list]] = {}
                for gid, subs in per_source.items():
                    for parts in subs:
                        part = parts[w.global_index]
                        if part is None:
                            continue
                        for t in np.unique(part["times"]):
                            tmask = part["times"] == t
                            per_time.setdefault(int(t), {}).setdefault(
                                gid, []
                            ).append({k: v[tmask] for k, v in part.items()})
                plans.append((df_id, lo, per_time))
            for t in range(min(lo for _, lo, _ in plans), upper):
                for df_id, lo, per_time in plans:
                    if t < lo:
                        continue
                    deltas = {
                        gid: _cols_to_batch(parts, None)
                        for gid, parts in per_time.get(t, {}).items()
                    }
                    w.dataflows[df_id]["df"].step(t, deltas)
            for df_id, _lo, _pt in plans:
                w.dataflows[df_id]["frontier"] = upper
                w.dataflows[df_id]["df"].frontier = upper
            return None

        try:
            _run_on_workers(self.workers, advance)
        except MeshError as e:
            return p.CommandErr(f"MeshError: sharded process_to: {e}")
        except Exception as e:
            return p.CommandErr(f"sharded process_to failed: {e}")
        for df_id, _lo, _ps in pending:
            self.sharded_dataflows[df_id]["frontier"] = upper
        return p.Frontiers(self._uppers())

    def _peek(self, cmd: p.Peek):
        if self.sharded:
            st = self.sharded_dataflows.get(cmd.dataflow_id)
            if st is None:
                return p.PeekResponse(
                    cmd.uuid, None, f"unknown dataflow {cmd.dataflow_id}"
                )

            def peek(w: ShardWorker):
                # worker threads have no thread-local span: this parents
                # under the adopted clusterd command span (obs/spans.py)
                with TRACER.span(f"worker{w.global_index}:peek"):
                    return w.dataflows[cmd.dataflow_id]["df"].peek(
                        cmd.index_id, at=cmd.at
                    )

            try:
                parts = _run_on_workers(self.workers, peek)
            except Exception as e:
                return p.PeekResponse(cmd.uuid, None, str(e))
            # a process-local multiset union; the controller merges processes
            rows = [r for part in parts for r in part]
            return p.PeekResponse(cmd.uuid, rows)
        st = self.dataflows.get(cmd.dataflow_id)
        if st is None:
            return p.PeekResponse(cmd.uuid, None, f"unknown dataflow {cmd.dataflow_id}")
        try:
            rows = st["df"].peek(cmd.index_id, at=cmd.at)
            return p.PeekResponse(cmd.uuid, rows)
        except Exception as e:
            return p.PeekResponse(cmd.uuid, None, str(e))

    def _uppers(self) -> dict:
        if self.sharded:
            return {k: st["frontier"] for k, st in self.sharded_dataflows.items()}
        return {k: st["frontier"] for k, st in self.dataflows.items()}

    def _fetch_stats(self) -> p.StatsReport:
        """Merge this process's introspection stats across its local workers
        (sum elapsed/invocations/rows per operator, sum partitioned
        arrangement sizes) — the per-process half of the partitioned-peek-
        style merge the coordinator finishes across shard processes. Safe to
        read worker Dataflows directly: commands are serialized under the
        handler lock and no worker job is in flight here."""
        operators: dict = {}
        arrangements: dict = {}

        def add_df(df_id: str, df) -> None:
            for obj, op_i, typ, elapsed, inv in df.operator_info():
                cur = operators.setdefault((df_id, obj, op_i, typ), [0] * 5)
                cur[0] += int(elapsed)
                cur[1] += int(inv)
            for obj, op_i, typ, rin, rout, retries in df.operator_rates():
                cur = operators.setdefault((df_id, obj, op_i, typ), [0] * 5)
                cur[2] += int(rin)
                cur[3] += int(rout)
                cur[4] += int(retries)
            for obj, op_i, name, nb, cap, rec, b in df.arrangement_info():
                cur = arrangements.setdefault((df_id, obj, op_i, name), [0] * 4)
                cur[0] += int(nb)
                cur[1] += int(cap)
                cur[2] += int(rec)
                cur[3] += int(b)

        dataflows = []
        if self.sharded:
            procname = f"shard{self.mesh.process_index}"
            for w in self.workers:
                for df_id, wst in w.dataflows.items():
                    add_df(df_id, wst["df"])
            for df_id, st in self.sharded_dataflows.items():
                dataflows.append((df_id, int(st["frontier"]), int(st["as_of"])))
        else:
            procname = "clusterd"
            for df_id, st in self.dataflows.items():
                add_df(df_id, st["df"])
                dataflows.append(
                    (df_id, int(st["frontier"]), int(st.get("as_of", 0)))
                )
        return p.StatsReport(
            procname,
            tuple(k + tuple(v) for k, v in operators.items()),
            tuple(k + tuple(v) for k, v in arrangements.items()),
            tuple(dataflows),
            obs_metrics.REGISTRY.snapshot(),
        )


def _cols_to_batch(col_dicts, advance_to) -> UpdateBatch:
    parts = col_dicts if isinstance(col_dicts, list) else [col_dicts]
    datas, times, diffs = [], [], []
    ncols = max(
        (len([k for k in c if k.startswith("c")]) for c in parts), default=0
    )
    for c in parts:
        datas.append([c[f"c{i}"] for i in range(ncols)])
        t = c["times"]
        if advance_to is not None:
            t = np.maximum(t, np.uint64(advance_to))
        times.append(t)
        diffs.append(c["diffs"])
    cols = tuple(
        np.concatenate([d[i] for d in datas]) for i in range(ncols)
    )
    return UpdateBatch.build(
        (), cols, np.concatenate(times), np.concatenate(diffs)
    )


def serve(host: str, port: int, mesh_port: int | None = None):
    """Listen for controller connections (thread per connection; command
    handling is serialized by a lock — the worker loop is single-threaded as
    in the reference, but a newer-generation controller can always get in to
    fence the old one via its epoch). With `mesh_port`, the shard-mesh
    listener starts immediately so peer processes can dial before our own
    FormMesh command arrives."""
    state = ClusterState()
    lock = threading.Lock()
    if mesh_port is not None:
        state.mesh = WorkerMesh(host, mesh_port)
    # this process serves remote controllers: completed spans of traced
    # commands queue for shipment on the TracedResponse instead of rotting
    # in a ring buffer nobody in this process reads
    TRACER.set_shipping(True)
    TRACER.set_process(f"clusterd:{port}")
    srv = socket.create_server((host, port), reuse_port=False)
    srv.listen(4)
    # listener hygiene: accept() in this sandbox is not interrupted by a
    # listener close, so the loop must wake on a timeout to observe shutdown
    # (here: the closed socket raising OSError on the next accept call)
    srv.settimeout(1.0)
    _log.info("listening", host=host, port=port)

    def ident():
        """Fault-injection identity: known only once the mesh is formed (so
        handshakes with a fresh/restarted process are never faulted), and
        matching the controller's ReplicaClient label for the same link."""
        if state.mesh is not None and state.workers:
            return f"shard{state.mesh.process_index}"
        return None

    def client(conn):
        try:
            while True:
                me = ident()
                cmd = p.recv_frame(conn, link=("ctl", me) if me else None)
                if cmd is None:
                    break
                ctx = None
                if isinstance(cmd, p.Traced):
                    ctx, cmd = cmd.ctx, cmd.cmd
                if ctx is not None:
                    # dispatch under a command span parented by the remote
                    # context; worker jobs adopt the command span as THEIR
                    # parent, and everything completed ships back on the
                    # response envelope
                    with TRACER.adopt_scope(ctx):
                        with TRACER.span(
                            f"clusterd:{type(cmd).__name__}"
                        ) as sp:
                            with TRACER.adopt_scope((ctx[0], sp.id)):
                                with lock:
                                    resp = state.handle(cmd)
                    resp = p.TracedResponse(TRACER.drain_pending(), resp)
                else:
                    with lock:
                        resp = state.handle(cmd)
                me = ident()
                p.send_frame(conn, resp, link=(me, "ctl") if me else None)
        except (ConnectionError, OSError):
            pass
        finally:
            conn.close()

    while True:
        try:
            conn, _addr = srv.accept()
        except socket.timeout:
            continue
        except OSError:
            return  # listener closed: shut down the accept loop
        threading.Thread(target=client, args=(conn,), daemon=True).start()


def main() -> None:
    ap = argparse.ArgumentParser(prog="clusterd")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument(
        "--mesh-port",
        type=int,
        default=None,
        help="listen port of the sharded-replica worker mesh (cluster/mesh.py)",
    )
    ap.add_argument("--cpu", action="store_true", help="force CPU jax (tests)")
    args = ap.parse_args()
    # subprocess logs default to info (the listening line, mesh formation)
    # unless the operator's MZT_LOG spec already chose levels
    if not os.environ.get("MZT_LOG"):
        obs_log.set_default_level("info")
    # chaos tests: adopt the spawning process's seeded fault schedule so the
    # shard mesh runs under the same deterministic network simulation
    from . import faults

    faults.install_from_env()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")
    serve(args.host, args.port, mesh_port=args.mesh_port)


if __name__ == "__main__":
    main()
