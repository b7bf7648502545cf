#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the main path still runs on the chip.

    python chip_smoke.py [--sf 1]            # one TPU chip: phases 1-2 below
    python chip_smoke.py --fused             # ... and phase 3 after them (SF0.01)
    python chip_smoke.py --chips 4           # the device-mesh phase only (SF0.001)

One process owns the chip: the frontends start in-process with the calls
`python -m materialize_tpu serve` makes (Coordinator, serve, serve_pgwire on
port 0) and this script talks to them over real sockets.

  1. device   jax.devices()[0] must be a TPU, else exit non-zero at once.
  2. serve    (shipped defaults) CREATE SOURCE tpch ... SCALE FACTOR <sf> and
              CREATE MATERIALIZED VIEW q3 (TPC-H Q3) over pgwire; a second
              connection SUBSCRIBEs; three advance() ticks (one RF1+RF2 refresh
              each, the generator's own 0.1 % of ORDERS); after each, SELECT
              over pgwire and POST /api/sql and the subscriber's consolidated
              diffs must equal models.tpch.q3_oracle recomputed on the host
              from the generator's stores, exactly; the view's state must live
              on the TPU.
  3. fused    (only with --fused) ALTER SYSTEM SET enable_fused_render = true,
              the same view as q3_fused (one XLA program per tick), two more
              ticks; q3_fused, q3 and the oracle must agree row for row after
              each. Behind an option, at SF0.01 unless --sf says otherwise,
              because every capacity of the fused program scales with the
              snapshot: at SF0.1 the chip's compiler refuses the tick
              (RESOURCE_EXHAUSTED, 17.66G of 15.75G hbm; PERF.md, PR 25), and
              at SF0.01 it takes about five minutes to compile on top of
              phases 1-2.

Every line printed is one JSON object; the last is
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
Any failure is an uncaught exception: non-zero exit, traceback, no last line.
Seconds and compile counts are facts of this run, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import json
import socket
import struct
import sys
import time
import urllib.request
from decimal import Decimal

Q3_BODY = """
    SELECT l_orderkey, sum(l_extendedprice * (1 - l_discount)) AS revenue,
           o_orderdate, o_shippriority
    FROM customer, orders, lineitem
    WHERE c_mktsegment = 'BUILDING' AND c_custkey = o_custkey
      AND l_orderkey = o_orderkey AND o_orderdate < DATE '1995-03-15'
      AND l_shipdate > DATE '1995-03-15'
    GROUP BY l_orderkey, o_orderdate, o_shippriority"""



class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


def check(ok, what) -> None:
    # not `assert`: the checks must survive `python -O`
    if not ok:
        raise SmokeFailure(what)


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


# -- compile accounting (jax.monitoring) --------------------------------------


class Compiles:
    """Counts XLA programs requested, persistent-cache hits among them, and
    the seconds spent in backend compile (or cache retrieval)."""

    def __init__(self):
        from jax import monitoring

        self.programs = 0
        self.cache_hits = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return (self.programs, self.cache_hits, self.seconds)

    def since(self, snap: tuple = (0, 0, 0.0)) -> dict:
        p, h, s = self.programs - snap[0], self.cache_hits - snap[1], self.seconds - snap[2]
        return {"programs": p, "cache_hits": h, "compiled": p - h, "compile_seconds": round(s, 3)}


class Step:
    """`with Step(compiles, "hydrate", view="q3"):` prints one line with the
    wall seconds and the compile counts of the enclosed work."""

    def __init__(self, compiles: Compiles, name: str, **fields):
        self.compiles, self.name, self.fields = compiles, name, fields

    def __enter__(self):
        self.snap = self.compiles.snapshot()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, _exc, _tb):
        if exc_type is None:
            emit(
                step=self.name,
                seconds=round(time.perf_counter() - self.t0, 3),
                **self.fields,
                **self.compiles.since(self.snap),
            )
        return False


# -- raw protocol-v3 client (the tests/test_pgwire.py MiniPgClient shape) -----


class PgClient:
    def __init__(self, port: int):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=3000)
        self.sock.sendall(struct.pack(">II", 8, 80877103))  # SSLRequest
        check(self.sock.recv(1) == b"N", "SSLRequest not answered with N")
        params = b"user\x00smoke\x00database\x00materialize\x00\x00"
        payload = struct.pack(">I", 196608) + params
        self.sock.sendall(struct.pack(">I", len(payload) + 4) + payload)
        msgs = self.read_until(b"Z")
        check(any(t == b"R" for t, _ in msgs), "no AuthenticationOk")

    def _read_exact(self, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            check(chunk, "server hung up")
            buf += chunk
        return bytes(buf)

    def read_message(self):
        tag = self._read_exact(1)
        (n,) = struct.unpack(">I", self._read_exact(4))
        return tag, self._read_exact(n - 4) if n > 4 else b""

    def read_until(self, end_tag: bytes) -> list:
        out = []
        while True:
            t, p = self.read_message()
            out.append((t, p))
            if t == end_tag:
                return out

    def send_query(self, sql: str) -> None:
        payload = sql.encode() + b"\x00"
        self.sock.sendall(b"Q" + struct.pack(">I", len(payload) + 4) + payload)

    def query(self, sql: str) -> list:
        """Simple query; returns text rows. An ErrorResponse raises."""
        self.send_query(sql)
        rows = []
        for t, p in self.read_until(b"Z"):
            if t == b"E":
                raise RuntimeError(f"{sql.split()[0:3]}: {p!r}")
            if t == b"D":
                (n,) = struct.unpack(">H", p[:2])
                off, row = 2, []
                for _ in range(n):
                    (ln,) = struct.unpack(">i", p[off : off + 4])
                    off += 4
                    if ln < 0:
                        row.append(None)
                    else:
                        row.append(p[off : off + ln].decode())
                        off += ln
                rows.append(tuple(row))
        return rows

    def close(self) -> None:
        self.sock.sendall(b"X" + struct.pack(">I", 4))
        self.sock.close()


class Subscriber:
    """A second connection tailing `SUBSCRIBE <view> WITH (PROGRESS)`;
    consolidates CopyData diffs per row payload."""

    def __init__(self, port: int, view: str):
        self.client = PgClient(port)
        self.client.send_query(f"SUBSCRIBE {view} WITH (PROGRESS)")
        tag, payload = self.client.read_message()
        check(tag == b"H", f"expected CopyOutResponse, got {tag!r} {payload!r}")
        self.agg: dict = {}
        self.frontier = 0

    def read_past(self, ts: int) -> dict:
        """Consume the stream until a progress row says every update at
        times <= ts has been delivered; returns the consolidated rows."""
        while self.frontier <= ts:
            tag, p = self.client.read_message()
            check(tag == b"d", f"unexpected message {tag!r} mid-stream: {p!r}")
            f = p.decode().rstrip("\n").split("\t")
            if f[1] == "t":
                self.frontier = max(self.frontier, int(f[0]))
            else:
                cols = tuple(f[3:])
                self.agg[cols] = self.agg.get(cols, 0) + int(f[2])
        return {k: v for k, v in self.agg.items() if v != 0}

    def close(self) -> None:
        self.client.sock.sendall(b"H" + struct.pack(">I", 4))  # Flush ends the stream
        msgs = self.client.read_until(b"Z")
        check(any(t == b"c" for t, _ in msgs), "no CopyDone at stream end")
        self.client.close()


def http_sql(port: int, sql: str) -> list:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/sql",
        data=json.dumps({"query": sql}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=3000) as resp:
        check(resp.status == 200, f"POST /api/sql -> {resp.status}")
        doc = json.loads(resp.read())
    return doc["results"][-1]["rows"]


# -- the served deployment ----------------------------------------------------


class Served:
    """Coordinator + both frontends, started as `cmd_serve` starts them."""

    def __init__(self):
        from materialize_tpu.adapter import Coordinator
        from materialize_tpu.frontend import serve
        from materialize_tpu.frontend.pgwire import serve_pgwire

        self.coord = Coordinator()
        self.httpd = serve(self.coord, host="127.0.0.1", port=0)
        self.lock = self.httpd.RequestHandlerClass.lock
        self.pg_srv, _thread = serve_pgwire(
            self.coord, host="127.0.0.1", port=0, lock=self.lock,
            reactor=getattr(self.httpd, "reactor", None),
        )
        self.http_port = self.httpd.server_address[1]
        self.pg_port = self.pg_srv.getsockname()[1]
        self.sql = PgClient(self.pg_port)

    def advance(self) -> int:
        """One source tick under the frontend lock, as `serve --advance-every`
        does it; returns the tick's timestamp."""
        with self.lock:
            return self.coord.advance()

    def dataflow(self, view: str):
        gid = self.coord.catalog.get(view).global_id
        return next(df for g, df, _src in self.coord.dataflows if g == gid)

    def oracle(self) -> dict:
        """models.tpch.q3_oracle over the generator's live host stores."""
        from materialize_tpu.models import tpch

        gen = self.coord.generators[0][0]
        building = self.coord.catalog.dict.lookup("BUILDING")
        check(building is not None, "BUILDING is not in the catalog dictionary")
        want = tpch.q3_oracle(
            *tpch.q3_inputs(gen.live()),
            building_code=building,
        )
        return {k: v for k, v in want.items() if v != 0}

    def close(self) -> None:
        self.sql.close()
        self.pg_srv.close()
        self.httpd.shutdown()


def _q3_key(lk, rev, od, sp) -> tuple:
    """One served Q3 row (text or JSON values) -> the oracle's
    ((l_orderkey, o_orderdate day number, o_shippriority), revenue * 10^4)."""
    scaled = Decimal(str(rev)) * 10_000
    check(scaled == scaled.to_integral_value(), f"revenue {rev!r} is not scale-4")
    return (int(lk), int(od), int(sp)), int(scaled)


def rows_to_groups(rows) -> dict:
    out = {}
    for row in rows:
        k, v = _q3_key(*row)
        check(k not in out, f"duplicate group {k}")
        out[k] = v
    return out


def device_leaves(root) -> list:
    """Every jax.Array reachable from `root` (a dataflow, a trace)."""
    import jax

    seen, out, stack = set(), [], [root]
    while stack:
        o = stack.pop()
        if id(o) in seen:
            continue
        seen.add(id(o))
        if isinstance(o, jax.Array):
            out.append(o)
        elif isinstance(o, dict):
            stack.extend(o.values())
        elif isinstance(o, (list, tuple, set, frozenset)):
            stack.extend(o)
        elif hasattr(o, "__dict__") and not isinstance(o, type) and not callable(o):
            stack.extend(vars(o).values())
    return out


def assert_state_on(served: Served, view: str, device) -> dict:
    """The view's arranged state must be jax Arrays on `device`: a path that
    computed on host NumPy cannot pass."""
    leaves = device_leaves(served.dataflow(view))
    nbytes = sum(int(a.nbytes) for a in leaves)
    check(leaves and nbytes > 0, f"{view}: no device state found")
    off = [a for a in leaves if a.devices() != {device}]
    check(not off, f"{view}: {len(off)} state arrays not on {device}: {[a.devices() for a in off[:3]]}")
    return {"state_arrays": len(leaves), "state_bytes": nbytes, "state_device": str(device)}


def check_views(served: Served, views: list, subs: dict, ts: int, compiles: Compiles, tick: int) -> int:
    """After a tick: every view over pgwire and HTTP, and every subscriber's
    consolidated stream, must equal the oracle exactly."""
    with Step(compiles, "oracle", tick=tick):
        want = served.oracle()
    check(want, "Q3 is empty: the check would be vacuous")
    for view in views:
        with Step(compiles, "peek_pgwire", view=view, tick=tick, rows=len(want)):
            got = rows_to_groups(served.sql.query(f"SELECT * FROM {view}"))
        if got != want:
            raise SmokeFailure(_diff(f"{view} over pgwire", got, want))
        with Step(compiles, "peek_http", view=view, tick=tick):
            got = rows_to_groups(http_sql(served.http_port, f"SELECT * FROM {view}"))
        if got != want:
            raise SmokeFailure(_diff(f"{view} over http", got, want))
    for view, sub in subs.items():
        with Step(compiles, "subscribe_catch_up", view=view, tick=tick):
            agg = sub.read_past(ts)
        check(all(v == 1 for v in agg.values()), f"{view}: subscriber multiplicity != 1")
        got = rows_to_groups(agg.keys())
        if got != want:
            raise SmokeFailure(_diff(f"{view} subscriber", got, want))
    return len(want)


def _diff(what: str, got: dict, want: dict) -> str:
    missing = sorted(set(want) - set(got))[:3]
    extra = sorted(set(got) - set(want))[:3]
    wrong = [(k, got[k], want[k]) for k in sorted(set(got) & set(want)) if got[k] != want[k]][:3]
    return (
        f"{what} != q3_oracle: {len(got)} rows vs {len(want)}; "
        f"missing {missing} extra {extra} wrong {wrong}"
    )


def phase_serve(served: Served, sf: float, device, compiles: Compiles) -> tuple:
    """Phase 2: the shipped defaults. Returns (subscribers, ticks so far)."""
    from materialize_tpu.dataflow.fused import FusedDataflow

    sql = served.sql
    emit(phase="serve", sf=sf, render="default", pg_port=served.pg_port, http_port=served.http_port)
    with Step(compiles, "create_source", sf=sf):
        sql.query(f"CREATE SOURCE tpch FROM LOAD GENERATOR TPCH (SCALE FACTOR {sf:g})")
    with Step(compiles, "hydrate", view="q3"):
        sql.query("CREATE MATERIALIZED VIEW q3 AS" + Q3_BODY)
    check(not isinstance(served.dataflow("q3"), FusedDataflow), "q3 rendered fused under defaults")
    emit(view="q3", **assert_state_on(served, "q3", device))
    subs = {"q3": Subscriber(served.pg_port, "q3")}
    tick = 0
    for _ in range(3):
        tick += 1
        with Step(compiles, "tick", tick=tick, views=["q3"]):
            ts = served.advance()
        check_views(served, ["q3"], subs, ts, compiles, tick)
    emit(view="q3", **assert_state_on(served, "q3", device))
    return subs, tick


def phase_fused(served: Served, sf: float, device, compiles: Compiles, subs: dict, tick: int) -> None:
    """Phase 3: the same view rendered as one XLA program per tick."""
    from materialize_tpu.dataflow.fused import FusedDataflow

    sql = served.sql
    emit(phase="fused", sf=sf, render="fused")
    sql.query("ALTER SYSTEM SET enable_fused_render = true")
    # q3 already exports shared traces of these inputs, and a fused plan that
    # could import one yields to the host renderer (dataflow/fused.py; the
    # render logs a warning when it does). With sharing off the fused view
    # arranges its own inputs on the device: a cause stepped round here, not
    # repaired (PERF.md, PR 25)
    sql.query("ALTER SYSTEM SET enable_arrangement_sharing = false")
    with Step(compiles, "hydrate", view="q3_fused"):
        sql.query("CREATE MATERIALIZED VIEW q3_fused AS" + Q3_BODY)
    fused = served.dataflow("q3_fused")
    check(isinstance(fused, FusedDataflow), "q3_fused fell back to the host render")
    check(fused.n_shards == 1, f"q3_fused spans {fused.n_shards} shards")
    emit(view="q3_fused", retries=fused.retries, scale=fused._scale,
         **assert_state_on(served, "q3_fused", device))
    subs["q3_fused"] = Subscriber(served.pg_port, "q3_fused")
    for _ in range(2):
        tick += 1
        with Step(compiles, "tick", tick=tick, views=["q3", "q3_fused"]):
            ts = served.advance()
        check_views(served, ["q3", "q3_fused"], subs, ts, compiles, tick)
        emit(view="q3_fused", tick=tick, retries=fused.retries, scale=fused._scale)
    emit(view="q3_fused", **assert_state_on(served, "q3_fused", device))


def run_one_chip(sf: float, device, compiles: Compiles, pin_host_exchange: bool, fused: bool) -> None:
    """Phase 2, and phase 3 when asked, on one device."""
    served = Served()
    if pin_host_exchange:
        # several devices visible and no --chips: exchange_backend = auto
        # would shard the fused view over all of them by itself
        served.sql.query("ALTER SYSTEM SET exchange_backend = host")
        emit(pinned_to_first_device=True, device=str(device))
    subs, tick = phase_serve(served, sf, device, compiles)
    if fused:
        phase_fused(served, sf, device, compiles, subs, tick)
    for sub in subs.values():
        sub.close()
    served.close()


def shard_rows(df) -> dict:
    """Live rows per shard of every arranged input of a mesh-rendered fused
    dataflow: state is tiled n_shards x on axis 0, one slice per shard."""
    import numpy as np

    from materialize_tpu.arrangement.lsm import LsmBatches

    out = {}
    for path, st in df.state.items():
        if not isinstance(st, LsmBatches):
            continue
        per = np.zeros(df.n_shards, dtype=np.int64)
        for level in st.levels:
            live = np.asarray(level.diffs) != 0
            per += live.reshape(df.n_shards, -1).sum(axis=1)
        out[path] = [int(x) for x in per]
    return out


def run_mesh(sf: float, devices, compiles: Compiles) -> None:
    """The --chips phase: the fused Q3 view over a device mesh of all local
    devices, against the oracle and the same view on the host exchange plane."""
    from materialize_tpu.dataflow.fused import FusedDataflow

    n = len(devices)
    served = Served()
    sql = served.sql
    emit(phase="mesh", sf=sf, chips=n)
    sql.query("ALTER SYSTEM SET enable_fused_render = true")
    with Step(compiles, "create_source", sf=sf):
        sql.query(f"CREATE SOURCE tpch FROM LOAD GENERATOR TPCH (SCALE FACTOR {sf:g})")
    with Step(compiles, "hydrate", view="q3_mesh"):
        sql.query("CREATE MATERIALIZED VIEW q3_mesh AS" + Q3_BODY)
    df = served.dataflow("q3_mesh")
    check(isinstance(df, FusedDataflow), "q3_mesh fell back to the host render")
    check(df.n_shards == n, f"n_shards {df.n_shards} != {n} devices")
    members = sql.query("SELECT device, platform, in_mesh FROM mz_device_mesh")
    emit(mz_device_mesh=members)
    in_mesh = [r for r in members if r[2] == "t"]
    check(len(in_mesh) == n, members)
    check(all(r[1] == devices[0].platform for r in in_mesh), members)
    state_devices = set()
    for a in device_leaves(df.state):
        state_devices |= a.devices()
    check(state_devices == set(devices), state_devices)

    sql.query("ALTER SYSTEM SET exchange_backend = host")
    with Step(compiles, "hydrate", view="q3_host"):
        sql.query("CREATE MATERIALIZED VIEW q3_host AS" + Q3_BODY)
    host_df = served.dataflow("q3_host")
    check(isinstance(host_df, FusedDataflow) and host_df.n_shards == 1, "q3_host is not a one-shard fused view")

    subs = {"q3_mesh": Subscriber(served.pg_port, "q3_mesh")}
    for tick in (1, 2):
        with Step(compiles, "tick", tick=tick, views=["q3_mesh", "q3_host"]):
            ts = served.advance()
        check_views(served, ["q3_mesh", "q3_host"], subs, ts, compiles, tick)
        per_shard = shard_rows(df)
        emit(tick=tick, retries=df.retries, scale=df._scale, shard_rows=per_shard)
        # lineitem's is the largest arrangement; Q3 is keyed, so every shard
        # must hold part of it (and of every other arranged input)
        biggest = max(per_shard, key=lambda p: sum(per_shard[p]))
        check(all(c > 0 for c in per_shard[biggest]), (biggest, per_shard[biggest]))
    for sub in subs.values():
        sub.close()
    served.close()


def phase_device():
    """Phase 1. Returns (devices, Compiles)."""
    import jax

    import materialize_tpu  # noqa: F401  (x64 + the compile cache, before any compile)

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(
            f"chip_smoke: jax.devices()[0] is {d0.platform!r}, not a TPU; nothing was run",
            file=sys.stderr,
        )
        sys.exit(1)
    from materialize_tpu.utils.native import get_native

    emit(
        phase="device",
        platform=d0.platform,
        kind=d0.device_kind,
        count=len(devices),
        jax=jax.__version__,
        compile_cache_dir=jax.config.jax_compilation_cache_dir,
        host_consolidate="native" if get_native() is not None else "numpy",
    )
    return devices, Compiles()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=None,
                    help="TPC-H scale factor (default 1; 0.01 with --fused; 0.001 with --chips)")
    ap.add_argument("--fused", action="store_true",
                    help="also run phase 3, the fused render")
    ap.add_argument("--chips", type=int, default=1,
                    help="with N > 1: run only the device-mesh phase over N chips")
    args = ap.parse_args()

    t0 = time.perf_counter()
    devices, compiles = phase_device()
    if args.chips > 1:
        check(len(devices) == args.chips, f"--chips {args.chips} but {len(devices)} devices")
        run_mesh(args.sf if args.sf is not None else 0.001, devices, compiles)
        used = devices
    else:
        run_one_chip(
            args.sf if args.sf is not None else (0.01 if args.fused else 1.0),
            devices[0], compiles, pin_host_exchange=len(devices) > 1, fused=args.fused,
        )
        used = devices[:1]
    emit(total_seconds=round(time.perf_counter() - t0, 3), **compiles.since())
    d0 = used[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": len(used)}}), flush=True)


if __name__ == "__main__":
    main()
