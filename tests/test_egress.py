"""The streaming egress plane (materialize_tpu/egress/): push SUBSCRIBE over
pgwire COPY + HTTP NDJSON, and exactly-once FILE sinks.

Fast subset (tier-1, `-m egress`): parser surface, the bounded-queue
backpressure/shed contract (53400), snapshot/progress options, the pgwire
COPY stream end to end over a TPC-H Q3 MV (snapshot + 8 churn ticks
consolidating to the final peek), cancel (57014) and idle reaping (57P05),
HTTP NDJSON streaming + poll error surfacing, sink lifecycle for both
formats, durable boot rehydration, introspection relations and /metrics.

Depth tiers: the sink crash-matrix sweep (every durable op of the progress
protocol × both sink_commit_order values, slow+crashmatrix; a pinned-seed
subset rides tier-1) and the chaos faulty-link SUBSCRIBE run (slow+chaos).
"""

import csv
import io
import json
import os
import random
import socket
import struct
import sys
import threading
import time
import urllib.request

import pytest

from materialize_tpu.adapter import Coordinator
from materialize_tpu.errors import SubscriptionOverflow, sqlstate_of
from materialize_tpu.frontend import serve
from materialize_tpu.frontend.pgwire import serve_pgwire

sys.path.insert(0, os.path.dirname(__file__))
import tpch_q3  # noqa: E402
from test_pgwire import MiniPgClient  # noqa: E402

pytestmark = pytest.mark.egress

PINNED_SEED = 20260805
SEED = int(os.environ.get("CRASH_SEED", PINNED_SEED))


# -- wire helpers -------------------------------------------------------------


def _send_query(client: MiniPgClient, sql: str) -> None:
    """Send Q without waiting for ReadyForQuery (MiniPgClient.query blocks
    until Z, which never arrives while a SUBSCRIBE stream is live)."""
    payload = sql.encode() + b"\x00"
    client.sock.sendall(b"Q" + struct.pack(">I", len(payload) + 4) + payload)


def _parse_copy_line(payload: bytes):
    """One CopyData row -> (ts, progressed, diff, cols tuple-of-text)."""
    fields = payload.decode().rstrip("\n").split("\t")
    return int(fields[0]), fields[1] == "t", int(fields[2]), tuple(fields[3:])


def _sqlstate(err_payload: bytes) -> str:
    for field in err_payload.split(b"\x00"):
        if field.startswith(b"C"):
            return field[1:].decode()
    return ""


def _end_stream(client: MiniPgClient):
    """Graceful SUBSCRIBE end: any client message stops the stream; Flush is
    a no-op for run() afterwards. Returns the (tag, payload) list up to Z."""
    client.sock.sendall(b"H" + struct.pack(">I", 4))
    return client.read_until(b"Z")


def _consolidate_json_changelog(data: bytes) -> dict:
    """Sum mz_diff per distinct row payload (timestamps excluded): crashed
    and clean runs commit the same content at different ticks, so equality
    is defined over the consolidated multiset, not raw bytes."""
    agg: dict = {}
    for line in data.decode().splitlines():
        if not line.strip():
            continue
        obj = json.loads(line)
        diff = obj.pop("mz_diff")
        obj.pop("mz_timestamp")
        key = tuple(sorted(obj.items()))
        agg[key] = agg.get(key, 0) + diff
    return {k: v for k, v in agg.items() if v != 0}


def _consolidate_csv_changelog(data: bytes) -> dict:
    agg: dict = {}
    for row in csv.reader(io.StringIO(data.decode())):
        if not row:
            continue
        _ts, diff, cols = int(row[0]), int(row[1]), tuple(row[2:])
        agg[cols] = agg.get(cols, 0) + diff
    return {k: v for k, v in agg.items() if v != 0}


# -- parser surface -----------------------------------------------------------


def test_parse_subscribe_options():
    from materialize_tpu.sql import ast
    from materialize_tpu.sql.parser import parse_statement

    s = parse_statement("SUBSCRIBE mv")
    assert isinstance(s, ast.Subscribe) and s.snapshot and not s.progress
    s = parse_statement("SUBSCRIBE mv WITH (SNAPSHOT false, PROGRESS)")
    assert not s.snapshot and s.progress
    s = parse_statement("SUBSCRIBE TO mv WITH (SNAPSHOT true)")
    assert s.snapshot and not s.progress


def test_parse_create_drop_sink():
    from materialize_tpu.sql import ast
    from materialize_tpu.sql.parser import parse_statement

    s = parse_statement("CREATE SINK out FROM mv INTO FILE '/tmp/x.json' FORMAT JSON")
    assert isinstance(s, ast.CreateSink)
    assert (s.name, s.from_name, s.path, s.format) == ("out", "mv", "/tmp/x.json", "json")
    d = parse_statement("DROP SINK out")
    assert isinstance(d, ast.DropObject) and d.kind == "sink" and d.name == "out"


# -- the bounded queue itself -------------------------------------------------


def test_subscription_queue_unit():
    from materialize_tpu.egress import Subscription

    sub = Subscription("s1", "g1", "mv", None, ("a",), max_depth=3)
    assert sub.publish([(1, 1, (10,))], progress_ts=2)
    assert sub.pop(timeout=0) == (1, False, 1, (10,))
    assert sub.pop(timeout=0) == (2, True, 0, None)
    assert sub.pop(timeout=0) is None and sub.state == "active"
    # overflow: the whole tick is dropped, the state flips, drains raise
    assert not sub.publish([(3, 1, (i,)) for i in range(4)])
    assert sub.state == "shed" and sub.shed_count == 1
    with pytest.raises(SubscriptionOverflow) as ei:
        sub.pop(timeout=0)
    assert sqlstate_of(ei.value) == "53400"
    with pytest.raises(SubscriptionOverflow):
        sub.drain()
    # publish after shed reports "tear me down", enqueues nothing
    assert not sub.publish([(4, 1, (0,))])
    # close is idempotent and terminal
    sub2 = Subscription("s2", "g1", "mv", None, ("a",))
    sub2.close("cancelled")
    sub2.close("dropped")
    assert sub2.state == "cancelled"
    assert not sub2.publish([(1, 1, (0,))])


def test_subscription_snapshot_is_not_backlog():
    """A snapshot larger than max_depth is delivered whole (both drain
    shapes); the bound still holds for what is published after it."""
    from materialize_tpu.egress import Subscription

    snap = [(1, 1, (i,)) for i in range(10)]
    sub = Subscription("s1", "g1", "mv", None, ("a",), max_depth=3)
    assert sub.publish(snap, progress_ts=2, snapshot=True)
    assert sub.state == "active" and sub.queue_depth() == 11  # all pending
    # later publishes count, alone: 3 fit beside the undrained snapshot
    assert sub.publish([(2, 1, (100 + i,)) for i in range(3)])
    got = [sub.pop(timeout=0) for _ in range(4)]
    assert got == [(1, False, 1, (i,)) for i in range(4)]
    fr = sub.pop_frame("ndjson", timeout=0)  # the rest of the snapshot + marker
    assert fr.count == 7 and sub.queue_depth() == 3
    # draining the snapshot freed no depth: the 4th counted update sheds
    assert not sub.publish([(3, 1, (0,))])
    assert sub.state == "shed" and sub.queue_depth() == 0
    with pytest.raises(SubscriptionOverflow):
        sub.pop(timeout=0)


def test_coordinator_subscribe_snapshot_larger_than_depth():
    c = Coordinator()
    c.execute("CREATE TABLE t (a int)")
    c.execute("INSERT INTO t VALUES " + ", ".join(f"({i})" for i in range(12)))
    c.execute("CREATE MATERIALIZED VIEW mv AS SELECT a FROM t")
    c.configs.set("subscribe_queue_depth", 4)
    out = c.execute("SUBSCRIBE mv WITH (PROGRESS)")
    sub = out.subscription
    assert sub.state == "active" and out.status in c.subscriptions
    c.execute("INSERT INTO t VALUES (100)")  # one update behind: within depth
    msgs = sub.drain()
    rows = sorted(m[3][0] for m in msgs if not m[1])
    assert rows == list(range(12)) + [100] and sub.state == "active"
    for j in range(6):  # nobody drains: the backlog bound still sheds
        c.execute(f"INSERT INTO t VALUES ({200 + j})")
    assert sub.state == "shed" and out.status not in c.subscriptions


def test_coordinator_sheds_slow_subscriber_53400():
    c = Coordinator()
    c.execute("CREATE TABLE t (a int)")
    c.execute("CREATE MATERIALIZED VIEW mv AS SELECT a FROM t")
    c.configs.set("subscribe_queue_depth", 4)
    out = c.execute("SUBSCRIBE mv")
    assert out.kind == "subscribe"
    sub, sid = out.subscription, out.status
    assert sid in c.subscriptions
    for j in range(6):  # nobody drains: the 5th update overflows depth 4
        c.execute(f"INSERT INTO t VALUES ({j})")
    assert sub.state == "shed" and sub.shed_count == 1
    assert sid not in c.subscriptions  # coordinator tore it down at the tick
    with pytest.raises(SubscriptionOverflow) as ei:
        sub.pop(timeout=0)
    assert sqlstate_of(ei.value) == "53400"
    assert c.overload.get("subscribe_sheds") >= 1


# -- coordinator-level subscribe lifecycle ------------------------------------


def test_subscribe_snapshot_deltas_and_progress():
    c = Coordinator()
    c.execute("CREATE TABLE t (a int)")
    c.execute("INSERT INTO t VALUES (1)")
    c.execute("CREATE MATERIALIZED VIEW mv AS SELECT a, count(*) AS n FROM t GROUP BY a")
    out = c.execute("SUBSCRIBE mv WITH (PROGRESS)")
    sub = out.subscription
    assert out.columns == ("a", "n")
    msgs = sub.drain()
    assert [m[3] for m in msgs if not m[1]] == [(1, 1)]  # the snapshot
    assert any(m[1] for m in msgs)  # initial progress marker
    c.execute("INSERT INTO t VALUES (1)")
    msgs = sub.drain()
    deltas = sorted((m[3], m[2]) for m in msgs if not m[1])
    assert deltas == [((1, 1), -1), ((1, 2), 1)]  # count retract + assert
    progress = [m for m in msgs if m[1]]
    assert progress and all(m[2] == 0 and m[3] is None for m in progress)
    # every data timestamp precedes the tick's progress marker
    assert max(m[0] for m in msgs if not m[1]) < progress[-1][0]
    c.teardown_subscription(out.status)
    assert out.status not in c.subscriptions and sub.state == "cancelled"


def test_subscribe_without_snapshot():
    c = Coordinator()
    c.execute("CREATE TABLE t (a int)")
    c.execute("INSERT INTO t VALUES (7)")
    c.execute("CREATE MATERIALIZED VIEW mv AS SELECT a FROM t")
    out = c.execute("SUBSCRIBE mv WITH (SNAPSHOT false)")
    sub = out.subscription
    assert [m for m in sub.drain() if not m[1]] == []  # no snapshot rows
    c.execute("INSERT INTO t VALUES (8)")
    assert [m[3] for m in sub.drain() if not m[1]] == [(8,)]
    c.teardown_subscription(out.status)


def test_subscribe_ad_hoc_view_uses_hidden_mv():
    """Subscribing to a non-materialized view plants a hidden MV and tears
    it (and its trace holds) down with the subscription."""
    c = Coordinator()
    c.execute("CREATE TABLE t (a int)")
    c.execute("CREATE VIEW v AS SELECT a + 1 AS b FROM t")
    out = c.execute("SUBSCRIBE v")
    sub = out.subscription
    assert sub.hidden_mv is not None
    assert any(
        i.name == sub.hidden_mv and i.kind == "materialized_view"
        for i in c.catalog.items.values()
    )
    c.execute("INSERT INTO t VALUES (41)")
    assert [m[3] for m in sub.drain() if not m[1]] == [(42,)]
    c.teardown_subscription(out.status)
    assert not any(i.name == sub.hidden_mv for i in c.catalog.items.values())


def test_drop_closes_dependent_subscriptions():
    c = Coordinator()
    c.execute("CREATE TABLE t (a int)")
    c.execute("CREATE MATERIALIZED VIEW mv AS SELECT a FROM t")
    out = c.execute("SUBSCRIBE mv")
    c.execute("DROP MATERIALIZED VIEW mv")
    assert out.status not in c.subscriptions
    assert out.subscription.state == "dropped"  # clean end, not an error


# -- pgwire COPY streaming ----------------------------------------------------

def _row_text(row) -> tuple:
    """Render a decoded peek row the way _send_copy_row does."""
    out = []
    for v in row:
        if v is None:
            out.append("\\N")
        elif isinstance(v, bool):
            out.append("t" if v else "f")
        else:
            out.append(str(v))
    return tuple(out)


def test_pgwire_subscribe_tpch_q3_end_to_end():
    """The acceptance run: SUBSCRIBE a TPC-H Q3 MV over pgwire, drive 8
    churn ticks, and the concatenated snapshot+delta stream consolidates to
    exactly the final peek, in timestamp order."""
    lock = threading.Lock()
    coord = Coordinator()
    srv, _t = serve_pgwire(coord, port=0, lock=lock)
    try:
        cl = MiniPgClient(srv.getsockname()[1])
        cl.startup()
        _rows, _c, tags, errs = cl.query(tpch_q3.SOURCE_SQL)
        assert not errs
        _rows, _c, tags, errs = cl.query(tpch_q3.VIEW_SQL)
        assert not errs
        # subscribe before any churn: the snapshot is empty, every row of
        # the final state must arrive (and consolidate) through deltas
        _send_query(cl, "SUBSCRIBE q3 WITH (PROGRESS)")
        tag, _p = cl.read_message()
        assert tag == b"H"  # CopyOutResponse
        for _ in range(8):
            with lock:
                coord.advance()
        with lock:
            want_rows = coord.execute("SELECT * FROM q3").rows
        want = {}
        for row in want_rows:
            key = _row_text(row)
            want[key] = want.get(key, 0) + 1
        assert want  # Q3 at sf 0.001 is non-empty after 8 ticks
        agg: dict = {}
        ts_seen = []
        cl.sock.settimeout(30)

        def _ingest(payload: bytes):
            ts, progressed, diff, cols = _parse_copy_line(payload)
            ts_seen.append(ts)
            if not progressed:
                agg[cols] = agg.get(cols, 0) + diff

        while {k: v for k, v in agg.items() if v} != want:
            tag, p = cl.read_message()
            assert tag == b"d", f"unexpected message {tag!r} mid-stream"
            _ingest(p)
        msgs = _end_stream(cl)
        for tag, p in msgs:  # any rows that raced the shutdown handshake
            if tag == b"d":
                _ingest(p)
        assert {k: v for k, v in agg.items() if v} == want
        assert ts_seen == sorted(ts_seen), "updates must stream in ts order"
        tags = [t for t, _ in msgs]
        assert b"c" in tags  # CopyDone
        assert any(t == b"C" and p.startswith(b"SUBSCRIBE") for t, p in msgs)
        assert not coord.subscriptions  # the read hold is released
        # the connection is reusable after the stream ends
        rows, *_ = cl.query("SELECT count(*) FROM q3")
        assert rows == [(str(len(want_rows)),)]
        cl.close()
    finally:
        srv.close()


def test_pgwire_subscribe_cancel_57014():
    lock = threading.Lock()
    coord = Coordinator()
    srv, _t = serve_pgwire(coord, port=0, lock=lock)
    try:
        port = srv.getsockname()[1]
        cl = MiniPgClient(port)
        msgs = cl.startup()
        key = [p for t, p in msgs if t == b"K"][0]
        pid, secret = struct.unpack(">II", key)
        cl.query("CREATE TABLE t (a int); CREATE MATERIALIZED VIEW mv AS SELECT a FROM t")
        _send_query(cl, "SUBSCRIBE mv")
        assert cl.read_message()[0] == b"H"
        s = socket.create_connection(("127.0.0.1", port), timeout=5)
        s.sendall(struct.pack(">IIII", 16, 80877102, pid, secret))
        s.close()
        cl.sock.settimeout(10)
        msgs = cl.read_until(b"Z")
        errs = [p for t, p in msgs if t == b"E"]
        assert errs and _sqlstate(errs[0]) == "57014"
        assert not coord.subscriptions
        cl.close()
    finally:
        srv.close()


def test_pgwire_subscribe_idle_reaped_57p05():
    """The idle-session satellite: a SUBSCRIBE that delivered nothing and
    whose client sent nothing is reaped by the same session timeout."""
    lock = threading.Lock()
    coord = Coordinator()
    srv, _t = serve_pgwire(coord, port=0, lock=lock)
    try:
        cl = MiniPgClient(srv.getsockname()[1])
        cl.startup()
        cl.query("CREATE TABLE t (a int); CREATE MATERIALIZED VIEW mv AS SELECT a FROM t")
        cl.query("SET idle_in_transaction_session_timeout = 300")
        before = coord.overload.get("idle_timeouts")
        _send_query(cl, "SUBSCRIBE mv")  # empty MV: nothing will ever arrive
        assert cl.read_message()[0] == b"H"
        cl.sock.settimeout(10)
        msgs = cl.read_until(b"Z")
        errs = [p for t, p in msgs if t == b"E"]
        assert errs and _sqlstate(errs[0]) == "57P05"
        assert not coord.subscriptions  # the trace hold is released
        assert coord.overload.get("idle_timeouts") > before
        cl.sock.close()
    finally:
        srv.close()


# -- HTTP NDJSON streaming + poll ---------------------------------------------


@pytest.fixture
def http_server():
    coord = Coordinator()
    httpd = serve(coord, port=0)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{httpd.server_address[1]}", coord, httpd.server_address[1]
    httpd.shutdown()


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


class _NdjsonStream:
    """Raw-socket chunked-NDJSON reader for /api/subscribe/<id>/stream."""

    def __init__(self, port, sub_id, timeout=10):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=timeout)
        self.sock.sendall(
            (
                f"GET /api/subscribe/{sub_id}/stream HTTP/1.1\r\n"
                "Host: localhost\r\n\r\n"
            ).encode()
        )
        self.f = self.sock.makefile("rb")
        self.headers = b""
        while True:
            line = self.f.readline()
            self.headers += line
            if line in (b"\r\n", b""):
                break

    def next_line(self):
        """One NDJSON object, or None at end-of-stream."""
        size_line = self.f.readline()
        size = int(size_line.strip(), 16)
        if size == 0:
            self.f.readline()
            return None
        data = self.f.read(size)
        self.f.readline()
        return json.loads(data)

    def close(self):
        # the makefile object holds its own reference to the fd: both must
        # be closed for the TCP connection to actually die
        try:
            self.f.close()
        except OSError:
            pass
        self.sock.close()


def test_http_subscribe_ndjson_stream(http_server):
    base, coord, port = http_server
    _post(base, "/api/sql", {"query": "CREATE TABLE t (a int); INSERT INTO t VALUES (1)"})
    _post(base, "/api/sql", {"query": "CREATE MATERIALIZED VIEW mv AS SELECT a FROM t"})
    doc, status = _post(base, "/api/subscribe", {"query": "SUBSCRIBE mv"})
    assert status == 200
    sid = doc["subscription_id"]
    stream = _NdjsonStream(port, sid)
    assert b"200" in stream.headers.splitlines()[0]
    assert b"application/x-ndjson" in stream.headers
    obj = stream.next_line()  # the snapshot
    assert obj == {"mz_timestamp": obj["mz_timestamp"], "mz_progressed": False,
                   "mz_diff": 1, "row": [1]}
    _post(base, "/api/sql", {"query": "INSERT INTO t VALUES (2)"})
    obj = stream.next_line()
    assert obj["row"] == [2] and obj["mz_diff"] == 1
    # client walks away: the next emits fail and the server tears down
    stream.close()
    deadline = time.time() + 10
    while sid in coord.subscriptions and time.time() < deadline:
        _post(base, "/api/sql", {"query": "INSERT INTO t VALUES (3)"})
        time.sleep(0.1)
    assert sid not in coord.subscriptions
    # a missing id is a 404, not a hang
    bad = _NdjsonStream(port, "nope")
    assert b"404" in bad.headers.splitlines()[0]
    bad.close()


def test_http_stream_idle_reaps_57p05(http_server):
    base, coord, port = http_server
    _post(base, "/api/sql", {"query": "CREATE TABLE t (a int)"})
    _post(base, "/api/sql", {"query": "CREATE MATERIALIZED VIEW mv AS SELECT a FROM t"})
    coord.configs.set("idle_in_transaction_session_timeout", 300)
    try:
        doc, _ = _post(base, "/api/subscribe", {"query": "SUBSCRIBE mv"})
        sid = doc["subscription_id"]
        stream = _NdjsonStream(port, sid)
        obj = stream.next_line()  # terminal error line, then end-of-stream
        assert obj["code"] == "57P05"
        assert stream.next_line() is None
        stream.close()
        assert sid not in coord.subscriptions
    finally:
        coord.configs.set("idle_in_transaction_session_timeout", 60000)


def test_http_poll_surfaces_shed_53400(http_server):
    base, coord, _port = http_server
    _post(base, "/api/sql", {"query": "CREATE TABLE t (a int)"})
    _post(base, "/api/sql", {"query": "CREATE MATERIALIZED VIEW mv AS SELECT a FROM t"})
    doc, _ = _post(base, "/api/subscribe", {"query": "SUBSCRIBE mv"})
    sid = doc["subscription_id"]
    # flip the subscription to shed while it is still registered — the
    # window between the overflow and the poll observing it
    coord.subscriptions[sid].state = "shed"
    try:
        urllib.request.urlopen(base + f"/api/subscribe/{sid}/poll")
        pytest.fail("poll of a shed subscription must not return 200")
    except urllib.error.HTTPError as e:
        body = json.loads(e.read())
        assert e.code == 400 and body["code"] == "53400"
    assert sid not in coord.subscriptions  # reported once, then torn down
    _doc, status = _post(base, "/api/sql", {"query": "SELECT 1"})
    assert status == 200  # the server is still healthy


# -- FILE sinks ---------------------------------------------------------------


def test_sink_json_lifecycle_nondurable(tmp_path):
    p = tmp_path / "out.json"
    c = Coordinator()
    c.execute("CREATE TABLE t (a int, b text)")
    c.execute("INSERT INTO t VALUES (1, 'x')")
    c.execute("CREATE MATERIALIZED VIEW mv AS SELECT a, b FROM t")
    c.execute(f"CREATE SINK snk FROM mv INTO FILE '{p}' FORMAT JSON")
    assert c.sinks and any(i.name == "snk" and i.kind == "sink"
                           for i in c.catalog.items.values())
    lines = [json.loads(ln) for ln in p.read_text().splitlines()]
    assert [(ln["a"], ln["b"], ln["mz_diff"]) for ln in lines] == [(1, "x", 1)]
    c.execute("INSERT INTO t VALUES (2, 'y')")
    c.execute("DELETE FROM t WHERE a = 1")
    got = _consolidate_json_changelog(p.read_bytes())
    want = {(("a", 2), ("b", "y")): 1}
    assert got == want
    # retraction really is a -1 line, not a rewrite
    assert any(json.loads(ln)["mz_diff"] == -1 for ln in p.read_text().splitlines())
    size = p.stat().st_size
    c.execute("DROP SINK snk")
    assert not c.sinks
    c.execute("INSERT INTO t VALUES (9, 'z')")
    assert p.stat().st_size == size  # dropped sinks stop appending
    assert not any(i.kind == "sink" for i in c.catalog.items.values())


def test_drop_source_cascades_to_sink(tmp_path):
    p = tmp_path / "out.csv"
    c = Coordinator()
    c.execute("CREATE TABLE t (a int)")
    c.execute("CREATE MATERIALIZED VIEW mv AS SELECT a FROM t")
    c.execute(f"CREATE SINK snk FROM mv INTO FILE '{p}' FORMAT CSV")
    c.execute("DROP MATERIALIZED VIEW mv")
    assert not c.sinks
    assert not any(i.kind == "sink" for i in c.catalog.items.values())


def test_sink_durable_reboot_resumes_exactly_once(tmp_path):
    d, p = tmp_path / "data", tmp_path / "out.csv"
    c1 = Coordinator(data_dir=str(d))
    c1.execute("CREATE TABLE t (a int, b text)")
    c1.execute("INSERT INTO t VALUES (1, 'x')")
    c1.execute("CREATE MATERIALIZED VIEW mv AS SELECT a, b FROM t")
    c1.execute(f"CREATE SINK snk FROM mv INTO FILE '{p}' FORMAT CSV")
    c1.execute("INSERT INTO t VALUES (2, 'y')")
    before = p.read_bytes()
    assert _consolidate_csv_changelog(before) == {("1", "x"): 1, ("2", "y"): 1}
    c2 = Coordinator(data_dir=str(d))
    # boot rehydration resumed from the progress register: no replay
    assert p.read_bytes() == before
    assert c2.sinks and any(i.name == "snk" for i in c2.catalog.items.values())
    c2.execute("INSERT INTO t VALUES (3, 'z')")
    after = p.read_bytes()
    assert after.startswith(before)
    assert _consolidate_csv_changelog(after) == {
        ("1", "x"): 1, ("2", "y"): 1, ("3", "z"): 1,
    }


# -- introspection + metrics --------------------------------------------------


def test_introspection_relations(tmp_path):
    p = tmp_path / "out.json"
    c = Coordinator()
    c.execute("CREATE TABLE t (a int)")
    c.execute("INSERT INTO t VALUES (1)")
    c.execute("CREATE MATERIALIZED VIEW mv AS SELECT a FROM t")
    out = c.execute("SUBSCRIBE mv")
    c.execute(f"CREATE SINK snk FROM mv INTO FILE '{p}' FORMAT JSON")
    subs = c.execute("SELECT * FROM mz_subscriptions").rows
    assert [(r[0], r[1], r[2]) for r in subs] == [(out.status, "mv", "active")]
    assert subs[0][3] >= 1  # the snapshot is queued, undrained
    sinks = c.execute("SELECT * FROM mz_sinks").rows
    assert [(r[1], r[2], r[3], r[4]) for r in sinks] == [("snk", "mv", str(p), "json")]
    assert sinks[0][6] >= 1  # emitted_updates counts the snapshot
    c.teardown_subscription(out.status)
    assert c.execute("SELECT * FROM mz_subscriptions").rows == []


def test_egress_metrics_exported(http_server, tmp_path):
    base, coord, _port = http_server
    _post(base, "/api/sql", {"query": "CREATE TABLE t (a int); INSERT INTO t VALUES (1)"})
    _post(base, "/api/sql", {"query": "CREATE MATERIALIZED VIEW mv AS SELECT a FROM t"})
    _post(base, "/api/subscribe", {"query": "SUBSCRIBE mv"})
    p = tmp_path / "m.json"
    _post(base, "/api/sql", {"query": f"CREATE SINK snk FROM mv INTO FILE '{p}' FORMAT JSON"})
    with urllib.request.urlopen(base + "/metrics") as r:
        text = r.read().decode()
    for name in (
        "mzt_egress_subscribe_updates_total",
        "mzt_egress_subscribe_sheds_total",
        "mzt_egress_sink_frames_total",
        "mzt_egress_sink_bytes_total",
        "mzt_egress_subscription_queue_depth",
        "mzt_egress_subscription_delivered",
        "mzt_egress_sink_progress_frontier",
        "mzt_egress_sink_emitted_updates",
    ):
        assert name in text, f"missing metric family {name}"


# -- the sink crash matrix ----------------------------------------------------

_INSERTS = [(j % 3, j * 10) for j in range(1, 7)]


def _run_sink_workload(d, path, order):
    """The canonical sink workload: grouped-sum MV (so ticks retract AND
    assert), a JSON FILE sink, six single-statement inserts."""
    c = Coordinator(data_dir=str(d))
    c.configs.set("sink_commit_order", order)
    c.execute("CREATE TABLE t (k int, v int)")
    c.execute("CREATE MATERIALIZED VIEW mv AS SELECT k, sum(v) AS s FROM t GROUP BY k")
    c.execute(f"CREATE SINK snk FROM mv INTO FILE '{path}' FORMAT JSON")
    for k, v in _INSERTS:
        c.execute(f"INSERT INTO t VALUES ({k}, {v})")
    return c


def _sink_ops(trace) -> list:
    """Durable-op indices belonging to the sink progress protocol: the
    changelog appends plus every blob/cas op of the progress shard."""
    return [
        n for (n, label, key, _d) in trace
        if label == "file.append" or "_progress" in str(key)
    ]


def _crash_one_point(tmp_path, order, k, reference):
    from materialize_tpu.persist import crashpoints
    from materialize_tpu.persist.crashpoints import CrashPlan, CrashPointReached

    d = tmp_path / f"{order}-{k}"
    path = tmp_path / f"{order}-{k}.json"
    plan = CrashPlan(SEED, crash_at=k)
    crashpoints.install(plan)
    try:
        _run_sink_workload(d, path, order)
        crashed = False
    except CrashPointReached:
        crashed = True
    finally:
        crashpoints.install(None)
    assert crashed, f"CRASH_SEED={SEED}: op {k} never fired for order={order}"
    # restart from the same data dir: boot-time rehydration repairs the
    # changelog from the progress register (note: boot runs under the
    # DEFAULT sink_commit_order — the register protocol must recover a
    # commit-first crash even when the resume emits emit-first)
    c2 = Coordinator(data_dir=str(d))
    c2.configs.set("sink_commit_order", order)
    assert any(i.name == "snk" for i in c2.catalog.items.values())
    done = len(c2.execute("SELECT * FROM t").rows)
    for kk, vv in _INSERTS[done:]:
        c2.execute(f"INSERT INTO t VALUES ({kk}, {vv})")
    got = _consolidate_json_changelog(path.read_bytes())
    assert got == reference, (
        f"CRASH_SEED={SEED} order={order} op={k} "
        f"shape={plan.shape_at(plan.trace[-1][1], k)}: changelog does not "
        f"consolidate to the no-crash run: {got} != {reference}"
    )


def _measure_and_reference(tmp_path, order):
    """No-crash run under a recording plan: yields the sink's durable-op
    schedule and the reference consolidated changelog."""
    from materialize_tpu.persist import crashpoints
    from materialize_tpu.persist.crashpoints import CrashPlan

    d0, p0 = tmp_path / f"ref-{order}", tmp_path / f"ref-{order}.json"
    plan = CrashPlan(SEED, crash_at=None)
    crashpoints.install(plan)
    try:
        c = _run_sink_workload(d0, p0, order)
    finally:
        crashpoints.install(None)
    reference = _consolidate_json_changelog(p0.read_bytes())
    # sanity: the reference consolidates to the MV's final contents
    mv = {}
    for k, s in c.execute("SELECT * FROM mv").rows:
        mv[(("k", int(k)), ("s", int(s)))] = mv.get((("k", int(k)), ("s", int(s))), 0) + 1
    assert reference == mv
    ops = _sink_ops(plan.trace)
    assert ops, "the workload must exercise the sink's durable ops"
    return ops, reference


def test_sink_crash_pinned_subset(tmp_path):
    """Tier-1: first append, a mid-protocol op, and the final op, for both
    commit orders (the full sweep is the crashmatrix marker)."""
    print(f"CRASH_SEED={SEED}")
    for order in ("emit-first", "commit-first"):
        ops, reference = _measure_and_reference(tmp_path, order)
        subset = sorted({ops[0], ops[len(ops) // 2], ops[-1]})
        for k in subset:
            _crash_one_point(tmp_path, order, k, reference)


@pytest.mark.slow
@pytest.mark.crashmatrix
def test_sink_crash_matrix_full_sweep(tmp_path):
    """Every durable op of the sink progress protocol, both orders: the
    recovered changelog must consolidate identically to the no-crash run."""
    print(f"CRASH_SEED={SEED}")
    for order in ("emit-first", "commit-first"):
        ops, reference = _measure_and_reference(tmp_path, order)
        for k in ops:
            _crash_one_point(tmp_path, order, k, reference)


# -- chaos: SUBSCRIBE over a faulty link --------------------------------------


@pytest.mark.slow
@pytest.mark.chaos
def test_subscribe_faulty_link_gap_free_prefix():
    """A SUBSCRIBE whose link dies mid-stream (seeded RST) delivers a
    gap-free, timestamp-ordered prefix — never a silent gap — and the
    server reaps the subscription on the broken connection."""
    seed = int(os.environ.get("FAULT_SEED", PINNED_SEED))
    print(f"FAULT_SEED={seed}")
    rnd = random.Random(seed)
    lock = threading.Lock()
    coord = Coordinator()
    srv, _t = serve_pgwire(coord, port=0, lock=lock)
    try:
        cl = MiniPgClient(srv.getsockname()[1])
        cl.startup()
        cl.query("CREATE TABLE t (a int); CREATE MATERIALIZED VIEW mv AS SELECT a FROM t")
        _send_query(cl, "SUBSCRIBE mv")
        assert cl.read_message()[0] == b"H"
        for j in range(1, 16):  # churn arrives while the client reads
            with lock:
                coord.execute(f"INSERT INTO t VALUES ({j})")
        kill_after = rnd.randint(3, 12)
        received = []
        cl.sock.settimeout(10)
        while len(received) < kill_after:
            tag, p = cl.read_message()
            assert tag == b"d"
            ts, progressed, diff, cols = _parse_copy_line(p)
            if progressed:
                continue
            received.append((ts, diff, int(cols[0])))
        # the link dies: RST mid-stream, no goodbye
        cl.sock.setsockopt(
            socket.SOL_SOCKET, socket.SO_LINGER, struct.pack("ii", 1, 0)
        )
        cl.sock.close()
        # gap-free prefix: exactly 1..m, every diff +1, timestamps ordered
        assert [v for (_ts, _d, v) in received] == list(
            range(1, len(received) + 1)
        )
        assert all(d == 1 for (_ts, d, _v) in received)
        ts_seen = [ts for (ts, _d, _v) in received]
        assert ts_seen == sorted(ts_seen)
        deadline = time.time() + 10
        while coord.subscriptions and time.time() < deadline:
            time.sleep(0.05)
        assert not coord.subscriptions  # reaped: the read hold is released
    finally:
        srv.close()
