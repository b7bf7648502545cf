"""The reactor serving plane (materialize_tpu/serve/): event-loop pgwire +
HTTP frontends sharing one selector loop, with SUBSCRIBE fan-out through the
shared cursor ring.

Fast tier-1 subset: backend flip via the frontend_backend dyncfg,
partial-write resumption under EVENT_WRITE, half-open peer teardown, cursor
retention shed (53400) over the wire, max_subscriptions_per_user admission
(53300, retryable), the encode-once O(ticks) contract, and thread-vs-reactor
byte-identity on the canonical churn workload (snapshot + 8 insert/delete
ticks) for both pgwire and HTTP chunked streams.

The seeded 10k-subscriber churn storm (bounded RSS, gap-free prefixes,
documented-SQLSTATE-only failures, byte-identical wire drain across both
backends) is marked saturation+slow; replay with
`SATURATION_SEED=<n> python -m pytest tests/test_serve.py -m saturation`.
"""

from __future__ import annotations

import json
import os
import random
import resource
import socket
import struct
import sys
import threading
import time
import urllib.request

import pytest

from materialize_tpu.adapter import Coordinator
from materialize_tpu.errors import SqlError, sqlstate_of
from materialize_tpu.frontend import serve
from materialize_tpu.frontend.pgwire import (
    PgServer,
    resolve_frontend_backend,
    serve_pgwire,
)
from materialize_tpu.serve import Reactor, ReactorHttpServer, ReactorPgServer

sys.path.insert(0, os.path.dirname(__file__))
from test_egress import _end_stream, _parse_copy_line, _send_query, _sqlstate  # noqa: E402
from test_pgwire import MiniPgClient  # noqa: E402

PINNED_SEED = 20260807
SEED = int(os.environ.get("SATURATION_SEED", PINNED_SEED))

DOCUMENTED_SQLSTATES = {"57014", "53300", "53400", "57P05"}


# -- wire helpers -------------------------------------------------------------


class RecordingPgClient(MiniPgClient):
    """MiniPgClient that captures every framed byte the server sends (the
    initial unframed SSL 'N' is constant and excluded on both backends)."""

    def __init__(self, port):
        super().__init__(port)
        self.raw = bytearray()

    def _read_exact(self, n):
        buf = super()._read_exact(n)
        self.raw += buf
        return buf


def _mask_backend_key(raw: bytes) -> bytes:
    """Zero the BackendKeyData payload (random cancel secret, per-process
    pid) so two runs of the same workload compare byte-identically."""
    out = bytearray()
    i = 0
    while i < len(raw):
        tag = raw[i : i + 1]
        (n,) = struct.unpack(">I", raw[i + 1 : i + 5])
        payload = raw[i + 5 : i + 1 + n]
        if tag == b"K":
            payload = b"\x00" * len(payload)
        out += tag + struct.pack(">I", n) + payload
        i += 1 + n
    return bytes(out)


def _pgcopy_lines(frame_data: bytes) -> list:
    """Parse a pre-encoded pgcopy frame (concatenated CopyData messages)
    into (ts, progressed, diff, cols) tuples."""
    lines = []
    i = 0
    while i < len(frame_data):
        assert frame_data[i : i + 1] == b"d", frame_data[i : i + 1]
        (n,) = struct.unpack(">I", frame_data[i + 1 : i + 5])
        lines.append(_parse_copy_line(frame_data[i + 5 : i + 1 + n]))
        i += 1 + n
    return lines


def _consolidate(lines) -> dict:
    """Sum diffs per row payload; a gap-free complete prefix consolidates
    exactly to the collection's current content."""
    agg: dict = {}
    for _ts, progressed, diff, cols in lines:
        if progressed:
            continue
        agg[cols] = agg.get(cols, 0) + diff
    return {k: v for k, v in agg.items() if v != 0}


def _read_copy_until_progress_past(client, sentinel_col: str):
    """Read stream messages until the progress marker that closes the tick
    carrying `sentinel_col`; returns all parsed copy lines on the way."""
    lines = []
    sentinel_ts = None
    while True:
        t, p = client.read_message()
        if t != b"d":
            continue
        line = _parse_copy_line(p)
        lines.append(line)
        ts, progressed, _diff, cols = line
        if not progressed and cols and cols[0] == sentinel_col:
            sentinel_ts = ts
        if progressed and sentinel_ts is not None and ts > sentinel_ts:
            return lines


def _post(base, path, doc):
    req = urllib.request.Request(
        base + path,
        data=json.dumps(doc).encode(),
        headers={"content-type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req) as r:
            return json.loads(r.read()), r.status
    except urllib.error.HTTPError as e:
        return json.loads(e.read()), e.code


def _wait_until(cond, timeout=10.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


# -- backend flip (frontend_backend dyncfg) -----------------------------------


def test_frontend_backend_dyncfg_flip():
    coord = Coordinator()
    # auto resolves to the reactor serving plane
    assert resolve_frontend_backend(coord) == "reactor"
    assert resolve_frontend_backend(coord, "thread") == "thread"
    with pytest.raises(ValueError):
        resolve_frontend_backend(coord, "bogus")

    coord.configs.set("frontend_backend", "thread")
    srv, _t = serve_pgwire(coord, port=0)
    assert isinstance(srv, PgServer) and not isinstance(srv, ReactorPgServer)
    httpd = serve(coord, port=0)
    assert not isinstance(httpd, ReactorHttpServer)
    srv.close()
    httpd.server_close()

    coord.configs.set("frontend_backend", "reactor")
    srv2, _t2 = serve_pgwire(coord, port=0)
    assert isinstance(srv2, ReactorPgServer)
    httpd2 = serve(coord, port=0)
    assert isinstance(httpd2, ReactorHttpServer)
    # both frontends stay live across the flip: run one statement each way
    cl = MiniPgClient(srv2.getsockname()[1])
    cl.startup()
    rows, _c, tags, errs = cl.query("SELECT 1")
    assert rows == [("1",)] and not errs
    cl.close()
    with urllib.request.urlopen(
        f"http://127.0.0.1:{httpd2.server_address[1]}/api/readyz"
    ) as r:
        assert r.status == 200
    srv2.close()
    httpd2.shutdown()


def test_shared_reactor_serves_both_frontends():
    """One selector loop hosts pgwire AND HTTP (the __main__ wiring)."""
    coord = Coordinator()
    lock = threading.Lock()
    httpd = serve(coord, port=0, lock=lock, backend="reactor")
    srv, _t = serve_pgwire(
        coord, port=0, lock=lock, backend="reactor", reactor=httpd.reactor
    )
    assert srv.reactor is httpd.reactor
    cl = MiniPgClient(srv.getsockname()[1])
    cl.startup()
    _rows, _c, tags, _e = cl.query("CREATE TABLE t (a int)")
    assert tags == ["CREATE TABLE"]
    doc, status = _post(
        f"http://127.0.0.1:{httpd.server_address[1]}",
        "/api/sql",
        {"query": "INSERT INTO t VALUES (1); SELECT a FROM t"},
    )
    assert status == 200 and doc["results"][-1]["rows"] == [[1]]
    cl.close()
    srv.close()
    httpd.shutdown()


# -- partial-write resumption -------------------------------------------------


class TinyBufClient(MiniPgClient):
    """Client with a tiny receive buffer: the server's first snapshot frame
    overflows the socket and must resume under EVENT_WRITE readiness."""

    def __init__(self, port):
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        self.sock.settimeout(30)
        self.sock.connect(("127.0.0.1", port))


def test_partial_write_resumption_gap_free():
    coord = Coordinator()
    lock = threading.Lock()
    srv, _t = serve_pgwire(coord, port=0, lock=lock, backend="reactor")
    try:
        with lock:
            coord.execute("CREATE TABLE big (a int, b text)")
            pad = "x" * 1000
            for base in range(0, 300, 100):
                vals = ", ".join(
                    f"({i}, '{pad}')" for i in range(base, base + 100)
                )
                coord.execute(f"INSERT INTO big VALUES {vals}")
            coord.execute("CREATE MATERIALIZED VIEW mv AS SELECT a, b FROM big")
        cl = TinyBufClient(srv.getsockname()[1])
        cl.startup()
        _send_query(cl, "SUBSCRIBE mv")
        t, _p = cl.read_message()
        assert t == b"H"  # CopyOutResponse
        # let the server hit a partial send and park on EVENT_WRITE
        time.sleep(0.3)
        seen = set()
        while len(seen) < 300:
            t, p = cl.read_message()
            assert t == b"d", t
            ts, progressed, diff, cols = _parse_copy_line(p)
            if not progressed:
                assert diff == 1 and cols[1] == pad
                seen.add(int(cols[0]))
        assert seen == set(range(300))  # gap-free, nothing lost mid-send
        msgs = _end_stream(cl)
        assert any(t == b"C" and p.startswith(b"SUBSCRIBE") for t, p in msgs)
        cl.close()
    finally:
        srv.close()


# -- half-open peer -----------------------------------------------------------


def test_half_open_peer_tears_subscription_down():
    coord = Coordinator()
    lock = threading.Lock()
    srv, _t = serve_pgwire(coord, port=0, lock=lock, backend="reactor")
    try:
        with lock:
            coord.execute("CREATE TABLE t (a int)")
            coord.execute("CREATE MATERIALIZED VIEW mv AS SELECT a FROM t")
        cl = MiniPgClient(srv.getsockname()[1])
        cl.startup()
        _send_query(cl, "SUBSCRIBE mv")
        t, _p = cl.read_message()
        assert t == b"H"
        _wait_until(lambda: len(coord.subscriptions) == 1, what="subscription")
        # half-open: the peer stops sending (FIN) but keeps reading
        cl.sock.shutdown(socket.SHUT_WR)
        _wait_until(
            lambda: not coord.subscriptions, what="subscription teardown"
        )
        _wait_until(
            lambda: srv.active_connections == 0, what="connection release"
        )
        # the server closed its side without writing an error
        try:
            tail = cl.sock.recv(65536)
            while tail:
                assert b"57014" not in tail and b"53400" not in tail
                tail = cl.sock.recv(65536)
        except OSError:
            pass
        cl.sock.close()
    finally:
        srv.close()


# -- cursor retention shed (53400) over the wire ------------------------------


def test_cursor_shed_53400_over_reactor(monkeypatch):
    import materialize_tpu.serve.pgserve as pgserve_mod

    coord = Coordinator()
    lock = threading.Lock()
    srv, _t = serve_pgwire(coord, port=0, lock=lock, backend="reactor")
    try:
        with lock:
            coord.execute("CREATE TABLE t (a int)")
            coord.execute("CREATE MATERIALIZED VIEW mv AS SELECT a FROM t")
            coord.configs.set("fanout_ring_ticks", 2)
        # freeze the pump so the connection's cursor cannot advance
        monkeypatch.setattr(pgserve_mod, "HIGH_WATER", 0)
        cl = MiniPgClient(srv.getsockname()[1])
        cl.startup()
        _send_query(cl, "SUBSCRIBE mv")
        t, _p = cl.read_message()
        assert t == b"H"
        _wait_until(lambda: len(coord.subscriptions) == 1, what="subscription")
        for j in range(6):  # ring keeps 2 ticks: the cursor falls off
            with lock:
                coord.execute(f"INSERT INTO t VALUES ({j})")
        # unfreeze: the next pump observes the shed cursor
        monkeypatch.setattr(pgserve_mod, "HIGH_WATER", 256 * 1024)
        msgs = cl.read_until(b"Z")
        errs = [p for t, p in msgs if t == b"E"]
        assert errs and _sqlstate(errs[0]) == "53400", msgs
        _wait_until(lambda: not coord.subscriptions, what="shed teardown")
        cl.close()
    finally:
        srv.close()


# -- max_subscriptions_per_user (53300, retryable) ----------------------------


def test_max_subscriptions_per_user_53300():
    from materialize_tpu.errors import TooManySubscriptions

    c = Coordinator()
    c.execute("CREATE TABLE t (a int)")
    c.execute("CREATE MATERIALIZED VIEW mv AS SELECT a FROM t")
    c.configs.set("max_subscriptions_per_user", 1)
    s_alice = c.new_session()
    s_alice.user = "alice"
    out = c.execute("SUBSCRIBE mv", s_alice)
    assert out.kind == "subscribe"
    s_alice2 = c.new_session()
    s_alice2.user = "alice"
    with pytest.raises(TooManySubscriptions) as ei:
        c.execute("SUBSCRIBE mv", s_alice2)
    assert sqlstate_of(ei.value) == "53300" and ei.value.retryable
    # another tenant still gets in; alice gets in again after teardown
    s_bob = c.new_session()
    s_bob.user = "bob"
    assert c.execute("SUBSCRIBE mv", s_bob).kind == "subscribe"
    c.teardown_subscription(out.status)
    s_alice3 = c.new_session()
    s_alice3.user = "alice"
    assert c.execute("SUBSCRIBE mv", s_alice3).kind == "subscribe"


def test_max_subscriptions_per_user_53300_http():
    coord = Coordinator()
    httpd = serve(coord, port=0, backend="reactor")
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        _post(base, "/api/sql", {"query": "CREATE TABLE t (a int)"})
        _post(
            base,
            "/api/sql",
            {"query": "CREATE MATERIALIZED VIEW mv AS SELECT a FROM t"},
        )
        _post(
            base,
            "/api/sql",
            {"query": "ALTER SYSTEM SET max_subscriptions_per_user = 1"},
        )
        doc, status = _post(
            base, "/api/subscribe", {"query": "SUBSCRIBE mv", "user": "alice"}
        )
        assert status == 200 and "subscription_id" in doc
        doc2, status2 = _post(
            base, "/api/subscribe", {"query": "SUBSCRIBE mv", "user": "alice"}
        )
        assert status2 == 503 and doc2["code"] == "53300", doc2
    finally:
        httpd.shutdown()


# -- encode-once: O(ticks), not O(subscribers x ticks) ------------------------


def test_fanout_encodes_once_per_tick_not_per_subscriber():
    from materialize_tpu.egress.fanout import _DELIVERED, _ENCODED

    c = Coordinator()
    c.execute("CREATE TABLE t (a int)")
    c.execute("CREATE MATERIALIZED VIEW mv AS SELECT a FROM t")
    K, T = 25, 6
    subs = [
        c.execute("SUBSCRIBE mv WITH (SNAPSHOT false, PROGRESS)")
        for _ in range(K)
    ]
    # flush the per-subscriber preamble frames (encoded once per subscriber
    # at subscribe time — O(K) once, never O(K) per tick)
    for out in subs:
        while out.subscription.pop_frame("pgcopy", timeout=0) is not None:
            pass
    e0 = _ENCODED.value(format="pgcopy")
    d0 = _DELIVERED.value(format="pgcopy")
    for j in range(T):
        c.execute(f"INSERT INTO t VALUES ({j})")
    frames = {}
    for out in subs:
        mine = []
        f = out.subscription.pop_frame("pgcopy", timeout=0)
        while f is not None:
            mine.append(f)
            f = out.subscription.pop_frame("pgcopy", timeout=0)
        frames[out.status] = mine
    encoded = _ENCODED.value(format="pgcopy") - e0
    delivered = _DELIVERED.value(format="pgcopy") - d0
    # every subscriber saw every tick...
    assert all(
        sum(f.count for f in mine) >= T for mine in frames.values()
    )
    assert delivered >= K * T
    # ...but each tick's frame was rendered once, shared by reference:
    # encode count scales with ticks (data + progress), never with K
    assert encoded <= 2 * T + 2, (encoded, delivered)
    # byte-identical fan-out: same tick, same frame bytes for everyone
    first = next(iter(frames.values()))
    for mine in frames.values():
        assert [f.data for f in mine] == [f.data for f in first]
    for out in subs:
        c.teardown_subscription(out.status)


# -- thread-vs-reactor differential: canonical churn workload -----------------

CHURN = [
    "INSERT INTO t VALUES (1, 'ins-1')",
    "INSERT INTO t VALUES (2, 'ins-2')",
    "DELETE FROM t WHERE a = 1",
    "INSERT INTO t VALUES (3, 'ins-3')",
    "INSERT INTO t VALUES (4, 'ins-4')",
    "DELETE FROM t WHERE a = 3",
    "INSERT INTO t VALUES (5, 'ins-5')",
    "DELETE FROM t WHERE a = 0",  # retracts the snapshot seed
]

SENTINEL = "424242"


def _setup_churn_coordinator(backend):
    coord = Coordinator()
    coord.configs.set("frontend_backend", backend)
    lock = threading.Lock()
    with lock:
        coord.execute("CREATE TABLE t (a int, b text)")
        coord.execute("INSERT INTO t VALUES (0, 'seed')")
        coord.execute("CREATE MATERIALIZED VIEW mv AS SELECT a, b FROM t")
    return coord, lock


def _run_pgwire_churn(backend) -> bytes:
    """The canonical workload over one backend; returns the masked byte
    stream the client received, from startup through final ReadyForQuery."""
    coord, lock = _setup_churn_coordinator(backend)
    srv, _t = serve_pgwire(coord, port=0, lock=lock)
    try:
        cl = RecordingPgClient(srv.getsockname()[1])
        cl.startup()
        _send_query(cl, "SUBSCRIBE mv WITH (PROGRESS)")
        # the churn starts once the server has accepted the SUBSCRIBE: sent
        # and not awaited, its as_of lands wherever the threads' timing puts
        # it among the churn statements, and the two streams differ by chance
        assert cl.read_message()[0] == b"H"  # CopyOutResponse
        for stmt in CHURN:
            with lock:
                coord.execute(stmt)
        with lock:
            coord.execute(f"INSERT INTO t VALUES ({SENTINEL}, 'done')")
        lines = _read_copy_until_progress_past(cl, SENTINEL)
        # gap-free prefix: the stream consolidates to the table's content
        assert _consolidate(lines) == {
            ("2", "ins-2"): 1,
            ("4", "ins-4"): 1,
            ("5", "ins-5"): 1,
            (SENTINEL, "done"): 1,
        }
        msgs = _end_stream(cl)
        assert any(t == b"C" and p.startswith(b"SUBSCRIBE") for t, p in msgs)
        cl.close()
        return _mask_backend_key(bytes(cl.raw))
    finally:
        srv.close()


def test_differential_pgwire_bytes_thread_vs_reactor():
    reactor_bytes = _run_pgwire_churn("reactor")
    thread_bytes = _run_pgwire_churn("thread")
    assert reactor_bytes == thread_bytes


def _run_http_churn(backend) -> bytes:
    """The canonical workload over the HTTP chunked stream; returns the raw
    chunked response BODY (headers carry Date/Server noise, the body is the
    contract)."""
    coord, lock = _setup_churn_coordinator(backend)
    httpd = serve(coord, port=0, lock=lock, backend=backend)
    serve_thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    serve_thread.start()
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        doc, status = _post(
            base, "/api/subscribe", {"query": "SUBSCRIBE mv WITH (PROGRESS)"}
        )
        assert status == 200
        sid = doc["subscription_id"]
        s = socket.create_connection(
            ("127.0.0.1", httpd.server_address[1]), timeout=30
        )
        s.sendall(
            (
                f"GET /api/subscribe/{sid}/stream HTTP/1.1\r\n"
                "Host: localhost\r\n\r\n"
            ).encode()
        )
        # wait for the response headers: the stream is attached before any
        # churn runs, on both backends
        raw = bytearray()
        while b"\r\n\r\n" not in raw:
            chunk = s.recv(65536)
            assert chunk, "stream closed before headers"
            raw += chunk
        for stmt in CHURN:
            with lock:
                coord.execute(stmt)
        with lock:
            coord.execute(f"INSERT INTO t VALUES ({SENTINEL}, 'done')")
        # dropping the collection ends the stream cleanly on both backends
        with lock:
            coord.execute("DROP MATERIALIZED VIEW mv")
        chunk = s.recv(65536)
        while chunk:
            raw += chunk
            chunk = s.recv(65536)
        s.close()
        body = bytes(raw).split(b"\r\n\r\n", 1)[1]
        assert body.endswith(b"0\r\n\r\n")
        return body
    finally:
        httpd.shutdown()


def test_differential_http_stream_thread_vs_reactor():
    reactor_body = _run_http_churn("reactor")
    thread_body = _run_http_churn("thread")
    assert reactor_body == thread_body
    # sanity: the identical bodies actually carry the churn
    assert SENTINEL.encode() in reactor_body


# -- the 10k-subscriber churn storm (saturation tier) -------------------------


def _storm(backend, rng_seed):
    """One full storm run against `backend`; returns the masked wire byte
    streams (for cross-backend comparison) plus invariant counters."""
    rng = random.Random(rng_seed)
    rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    coord = Coordinator()
    coord.configs.set("frontend_backend", backend)
    coord.configs.set("fanout_ring_ticks", 8)
    lock = threading.Lock()
    with lock:
        coord.execute("CREATE TABLE w (a int)")
        coord.execute("CREATE TABLE s (a int)")
        coord.execute("CREATE MATERIALIZED VIEW mv_wire AS SELECT a FROM w")
        coord.execute("CREATE MATERIALIZED VIEW mv_storm AS SELECT a FROM s")
        coord.execute("INSERT INTO w VALUES (0)")
    srv, _t = serve_pgwire(coord, port=0, lock=lock)
    clients = []
    try:
        # wire subscribers first (deterministic command order)
        for _ in range(8):
            cl = RecordingPgClient(srv.getsockname()[1])
            cl.startup()
            _send_query(cl, "SUBSCRIBE mv_wire WITH (PROGRESS)")
            t, _p = cl.read_message()
            assert t == b"H"
            clients.append(cl)
        # 10k coordinator-level subscribers: drainers get drained during the
        # storm and must see gap-free prefixes; lazy ones fall off the
        # 8-tick ring and must shed with exactly 53400
        live, drainers = {}, []
        def _subscribe():
            out = coord.execute("SUBSCRIBE mv_storm WITH (PROGRESS)")
            live[out.status] = out.subscription
            if rng.random() < 0.5:
                drainers.append(out.status)
        with lock:
            for _ in range(10_000):
                _subscribe()
        shed, drained_ok, w_expect = 0, 0, {("0",): 1}
        collected: dict = {}  # sid -> copy lines drained so far
        w_vals = iter(range(1, 7))
        for rnd in range(20):
            with lock:
                coord.execute(f"INSERT INTO s VALUES ({rnd})")
                for _ in range(20):  # churn: drop + add subscribers
                    sid = rng.choice(list(live))
                    coord.teardown_subscription(sid)
                    del live[sid]
                for _ in range(20):
                    _subscribe()
                if rnd % 3 == 0:  # canonical wire churn rides along
                    v = next(w_vals, None)
                    if v is not None:
                        coord.execute(f"INSERT INTO w VALUES ({v})")
                        w_expect[(str(v),)] = 1
            if rnd % 4 == 3:  # drain a cohort so their cursors advance
                for sid in rng.sample(drainers, 400):
                    sub = live.get(sid)
                    if sub is None:
                        continue
                    try:
                        f = sub.pop_frame("pgcopy", timeout=0)
                        while f is not None:
                            collected.setdefault(sid, []).extend(
                                _pgcopy_lines(f.data)
                            )
                            f = sub.pop_frame("pgcopy", timeout=0)
                    except SqlError as e:
                        assert sqlstate_of(e) in DOCUMENTED_SQLSTATES
        with lock:
            coord.execute(f"INSERT INTO w VALUES ({SENTINEL})")
        w_expect[(SENTINEL,)] = 1
        # wire drain: every client sees the identical gap-free stream
        streams = []
        for cl in clients:
            lines = _read_copy_until_progress_past(cl, SENTINEL)
            assert _consolidate(lines) == w_expect
            msgs = _end_stream(cl)
            assert any(
                t == b"C" and p.startswith(b"SUBSCRIBE") for t, p in msgs
            )
            cl.close()
            streams.append(_mask_backend_key(bytes(cl.raw)))
        # storm drain: every surviving subscriber's full drained history
        # (mid-storm cohort drains + this final drain) is a gap-free prefix
        # ending at the final frontier, so it consolidates to exactly the
        # table's final content; anything else fails with a documented
        # SQLSTATE only
        expected_s = {(str(v),): 1 for v in range(20)}
        for sid, sub in live.items():
            lines = collected.get(sid, [])
            try:
                f = sub.pop_frame("pgcopy", timeout=0)
                while f is not None:
                    lines.extend(_pgcopy_lines(f.data))
                    f = sub.pop_frame("pgcopy", timeout=0)
            except SqlError as e:
                assert sqlstate_of(e) in DOCUMENTED_SQLSTATES, e
                shed += 1
                continue
            assert _consolidate(lines) == expected_s, sid
            drained_ok += 1
        assert shed > 0 and drained_ok > 0, (shed, drained_ok)
        rss_delta = (
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss - rss0
        )
        assert rss_delta < 800_000, f"RSS grew {rss_delta}KB under the storm"
        return streams, shed, drained_ok
    finally:
        srv.close()


@pytest.mark.saturation
@pytest.mark.slow
def test_storm_10k_subscriber_churn_thread_vs_reactor():
    print(f"SATURATION_SEED={SEED}")
    reactor_streams, r_shed, r_ok = _storm("reactor", SEED)
    thread_streams, t_shed, t_ok = _storm("thread", SEED)
    # the same seed drives the same storm: both backends drain the same
    # bytes to every wire subscriber
    assert reactor_streams == thread_streams
    assert (r_shed, r_ok) == (t_shed, t_ok)
