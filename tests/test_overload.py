"""Overload protection and graceful degradation (tier-1 smoke surface).

Covers the serving path's budget/shed/cancel contract end to end:
session vars (SET/SHOW/RESET) carrying statement_timeout /
idle_in_transaction_session_timeout / max_result_size, cooperative
cancellation (pgwire CancelRequest secret keys; 57014 at tick-loop
checkpoints), admission control (max_connections + bounded coordinator
queues, 53300), balancer round-trip health probes, byte-budgeted source
ingest, FileBlob durability/escaping, and the listener-hygiene check.
The full storm lives in tests/test_saturation.py (slow tier).
"""

import os
import socket
import struct
import subprocess
import sys
import threading
import time

import pytest

from materialize_tpu.adapter import Coordinator
from materialize_tpu.errors import (
    AdmissionShed,
    QueryCanceled,
    ResultSizeExceeded,
    sqlstate_of,
)
from materialize_tpu.frontend.pgwire import serve_pgwire

sys.path.insert(0, os.path.dirname(__file__))
from test_pgwire import MiniPgClient  # noqa: E402


def _sqlstate(err_payload: bytes) -> str:
    """Extract the SQLSTATE field from an ErrorResponse payload."""
    for field in err_payload.split(b"\x00"):
        if field.startswith(b"C"):
            return field[1:].decode()
    return ""


@pytest.fixture
def pg():
    coord = Coordinator()
    srv, _t = serve_pgwire(coord, port=0)
    port = srv.getsockname()[1]
    client = MiniPgClient(port)
    client.startup()
    yield coord, srv, port, client
    try:
        client.close()
    except OSError:
        pass
    srv.close()


# -- session vars -------------------------------------------------------------


@pytest.mark.smoke
def test_overload_session_vars_set_show_reset(pg):
    coord, _srv, port, c = pg
    rows, *_ = c.query("SHOW statement_timeout")
    assert rows == [("0",)]
    c.query("SET statement_timeout = 30000")
    rows, *_ = c.query("SHOW statement_timeout")
    assert rows == [("30000",)]
    # per-connection: a second session is unaffected
    c2 = MiniPgClient(port)
    c2.startup()
    try:
        rows, *_ = c2.query("SHOW statement_timeout")
        assert rows == [("0",)]
    finally:
        c2.close()
    c.query("RESET statement_timeout")
    rows, *_ = c.query("SHOW statement_timeout")
    assert rows == [("0",)]
    # the other budget vars are settable/showable too
    for name, val in (
        ("max_result_size", "1048576"),
        ("idle_in_transaction_session_timeout", "60000"),
    ):
        c.query(f"SET {name} = {val}")
        rows, *_ = c.query(f"SHOW {name}")
        assert rows == [(val,)]
        c.query(f"RESET {name}")
    # unknown var errors cleanly
    _r, _c, _t, errors = c.query("RESET no_such_parameter")
    assert errors


# -- statement_timeout / cancellation ----------------------------------------


@pytest.mark.smoke
def test_statement_timeout_fires_mid_tick_57014(pg):
    coord, _srv, _port, c = pg
    c.query("CREATE TABLE t (a int)")
    c.query("INSERT INTO t VALUES (1), (2), (3)")
    c.query("SET statement_timeout = 1")
    # a multi-operator slow-path plan: the deadline has long passed by the
    # first checkpoint, so the tick loop aborts with the canonical SQLSTATE
    _r, _c2, _t, errors = c.query("SELECT t1.a FROM t t1, t t2, t t3")
    assert errors and _sqlstate(errors[0]) == "57014"
    c.query("RESET statement_timeout")
    rows, *_ = c.query("SELECT count(*) FROM t")
    assert rows == [("3",)]
    assert coord.overload.get("statement_timeouts") >= 1


@pytest.mark.smoke
def test_tick_loop_checkpoint_runs_between_dispatches():
    """The cancel hook fires between operator dispatches: a check installed
    on an ephemeral dataflow interrupts step() partway through the DAG."""
    from materialize_tpu.dataflow import Dataflow

    coord = Coordinator()
    coord.execute("CREATE TABLE t (a int)")
    coord.execute("INSERT INTO t VALUES (1), (2)")
    from materialize_tpu.adapter.coordinator import _collect_gets
    from materialize_tpu.sql.lower import lower_to_dataflow
    from materialize_tpu.sql.parser import parse_statement
    from materialize_tpu.transform import optimize

    stmt = parse_statement("SELECT t1.a FROM t t1, t t2")
    pq = coord.planner.plan_query(stmt.query)
    rel = optimize(pq.mir, coord.configs)
    src_gids = sorted(_collect_gets(rel))
    env = {g: coord.storage[g].dtypes for g in src_gids}
    desc = lower_to_dataflow("peek", rel, env, src_gids, as_of=1, until=2)
    df = Dataflow(desc)
    calls = {"n": 0}

    def check():
        calls["n"] += 1
        if calls["n"] >= 2:
            raise QueryCanceled("canceling statement due to statement timeout")

    df.cancel_check = check
    snaps = {g: coord.storage[g].snapshot(1) for g in src_gids}
    with pytest.raises(QueryCanceled):
        df.step(1, snaps)
    assert calls["n"] == 2  # interrupted BETWEEN dispatches, not at the end


@pytest.mark.smoke
def test_cancel_request_secret_key_validation(pg):
    coord, _srv, port, c = pg
    # fresh startup to grab this connection's BackendKeyData
    c2 = MiniPgClient(port)
    msgs = c2.startup()
    key = [p for t, p in msgs if t == b"K"][0]
    pid, secret = struct.unpack(">II", key)
    assert secret != 0

    def cancel(pid_, secret_):
        s = socket.create_connection(("127.0.0.1", port), timeout=5)
        s.sendall(struct.pack(">IIII", 16, 80877102, pid_, secret_))
        s.close()

    c2.query("CREATE TABLE ct (a int)")
    c2.query("INSERT INTO ct VALUES (1), (2)")
    # wrong secret: a complete no-op — the next statement runs normally
    cancel(pid, secret ^ 0x5A5A5A5A)
    rows, _cols, _tags, errors = c2.query("SELECT count(*) FROM ct")
    assert rows == [("2",)] and not errors
    assert coord.overload.get("cancel_requests_ignored") >= 1
    # unknown pid: also a no-op
    cancel(pid + 999, secret)
    rows, *_ = c2.query("SELECT count(*) FROM ct")
    assert rows == [("2",)]

    # right secret mid-statement: the statement dies with 57014 and the
    # connection stays usable
    fired = threading.Thread(target=lambda: (time.sleep(0.2), cancel(pid, secret)))
    fired.start()
    _r, _c3, _t, errors = c2.query(
        "SELECT t1.a FROM ct t1, ct t2, ct t3, ct t4, ct t5, ct t6"
    )
    fired.join()
    assert errors and _sqlstate(errors[0]) == "57014"
    rows, _c4, _t2, errors = c2.query("SELECT count(*) FROM ct")
    assert rows == [("2",)] and not errors

    c2.close()


@pytest.mark.smoke
def test_cancel_survives_script_statement_boundaries():
    """execute_stmt must NOT clear the cancel event: a cancel that lands
    during statement 1 of a script (after its checkpoints ran) still kills
    statement 2 at its entry checkpoint. The clear belongs to the protocol
    layer, once per query message."""
    coord = Coordinator()
    s = coord.new_session()
    coord.execute("CREATE TABLE bt (a int)", s)
    # simulate the cancel landing between statements of one script
    s.cancelled.set()
    with pytest.raises(QueryCanceled):
        coord.execute("SELECT 1 + 1", s)
    assert coord.overload.get("cancels_honored") == 1
    s.cancelled.clear()
    assert coord.execute("SELECT 1 + 1", s).rows == [(2,)]


# -- max_result_size ----------------------------------------------------------


@pytest.mark.smoke
def test_max_result_size_rejects_without_materializing(pg):
    coord, _srv, _port, c = pg
    c.query("CREATE TABLE big (a int)")
    c.query("INSERT INTO big VALUES (1), (2), (3), (4), (5), (6), (7), (8)")
    c.query("SET max_result_size = 200")
    # 8^3 = 512 rows ≫ 200 bytes: rejected with the documented SQLSTATE
    _r, _c2, _t, errors = c.query("SELECT t1.a FROM big t1, big t2, big t3")
    assert errors and _sqlstate(errors[0]) == "53400"
    c.query("RESET max_result_size")
    rows, *_ = c.query("SELECT count(*) FROM big")
    assert rows == [("8",)]
    assert coord.overload.get("result_size_rejections") >= 1


@pytest.mark.smoke
def test_materialize_counts_budget_aborts_expansion_early():
    """The budget stops COUNT EXPANSION itself: a single consolidated row
    with a huge multiplicity never becomes a huge list."""
    from materialize_tpu.dataflow.runtime import materialize_counts

    acc = {(1, 2): 10_000_000, (3, 4): 1}
    with pytest.raises(ResultSizeExceeded) as ei:
        materialize_counts(acc, "t", byte_budget=1024)
    # the abort happened within the first few expansions, not after 10M rows
    assert "aborted after ~" in str(ei.value)
    # unbudgeted expansion of a small acc still works
    assert materialize_counts({(7,): 3}, "t") == [(7,), (7,), (7,)]


# -- admission control --------------------------------------------------------


@pytest.mark.smoke
def test_admission_gate_sheds_beyond_depth():
    coord = Coordinator()
    coord.configs.set("coord_queue_depth", 2)
    entered, release = threading.Event(), threading.Event()

    def occupy():
        with coord.admission.admit():
            entered.set()
            release.wait(10)

    threads = [threading.Thread(target=occupy) for _ in range(2)]
    for t in threads:
        t.start()
    deadline = time.time() + 5
    while coord.admission.depth < 2 and time.time() < deadline:
        time.sleep(0.01)
    assert coord.admission.depth == 2
    # the line is full: the next admit sheds IMMEDIATELY (no blocking)
    t0 = time.time()
    with pytest.raises(AdmissionShed) as ei:
        with coord.admission.admit():
            pass
    assert time.time() - t0 < 5.0  # the occupiers hold the line for 10 s
    assert sqlstate_of(ei.value) == "53300" and ei.value.retryable
    release.set()
    for t in threads:
        t.join()
    assert coord.admission.depth == 0
    assert coord.overload.get("statement_sheds") == 1
    # live depth + sheds are SQL-visible
    rows = coord.execute(
        "SELECT value FROM mz_overload_counters WHERE name = 'statement_sheds'"
    ).rows
    assert rows == [(1,)]


@pytest.mark.smoke
def test_max_connections_rejects_with_53300(pg):
    coord, _srv, port, _c = pg
    coord.configs.set("max_connections", 1)
    try:
        extra = socket.create_connection(("127.0.0.1", port), timeout=5)
        extra.sendall(struct.pack(">II", 8, 80877103))  # SSLRequest probe
        resp = extra.recv(256)
        assert resp[:1] == b"E" and b"53300" in resp
        extra.close()
        assert coord.overload.get("connections_rejected") >= 1
    finally:
        coord.configs.set("max_connections", 256)
    # back under the limit: new connections work again
    c2 = MiniPgClient(port)
    c2.startup()
    rows, *_ = c2.query("SELECT 1 + 1")
    assert rows == [("2",)]
    c2.close()


def test_idle_session_timeout_57p05(pg):
    _coord, _srv, port, _c = pg
    c2 = MiniPgClient(port)
    c2.startup()
    c2.query("SET idle_in_transaction_session_timeout = 200")
    time.sleep(0.8)
    # the server terminated us: an ErrorResponse with 57P05, then EOF
    tag, payload = c2.read_message()
    assert tag == b"E" and _sqlstate(payload) == "57P05"
    c2.sock.close()


# -- balancer health probes ---------------------------------------------------


def test_balancer_skips_dead_backend_via_roundtrip():
    """A dead port in this sandbox accepts connect() (ROADMAP known facts);
    only the request/response probe rules it out."""
    from materialize_tpu.frontend.balancer import Balancer, pg_probe

    coord = Coordinator()
    coord.execute("CREATE TABLE bt (a int)")
    coord.execute("INSERT INTO bt VALUES (9)")
    srv, _t = serve_pgwire(coord, port=0)
    live = srv.getsockname()[1]
    # reserve a port, then close it — a genuinely dead backend address
    dead_sock = socket.create_server(("127.0.0.1", 0))
    dead = dead_sock.getsockname()[1]
    dead_sock.close()
    bal = Balancer(
        [("127.0.0.1", dead), ("127.0.0.1", live)], probe=pg_probe
    )
    try:
        for _ in range(3):  # round-robin lands on the dead slot first
            c = MiniPgClient(bal.port)
            c.startup()
            rows, *_ = c.query("SELECT a FROM bt")
            assert rows == [("9",)]
            c.close()
        assert bal.skipped_backends >= 1
    finally:
        bal.close()
        srv.close()


def test_balancer_probe_detects_saturated_backend():
    """A backend at max_connections answers the SSLRequest probe with an
    ErrorResponse instead of 'N' — the balancer treats it as dark."""
    from materialize_tpu.frontend.balancer import pg_probe

    coord = Coordinator()
    srv, _t = serve_pgwire(coord, port=0)
    port = srv.getsockname()[1]
    try:
        assert pg_probe(("127.0.0.1", port)) is True
        coord.configs.set("max_connections", 0)  # off → healthy
        assert pg_probe(("127.0.0.1", port)) is True
        # limit 0 disabled; use a held connection + limit 1 to saturate
        coord.configs.set("max_connections", 1)
        held = MiniPgClient(port)
        held.startup()
        assert pg_probe(("127.0.0.1", port)) is False
        held.close()
    finally:
        coord.configs.set("max_connections", 256)
        srv.close()


# -- source ingest backpressure ----------------------------------------------


def test_file_source_yields_under_byte_budget(tmp_path):
    coord = Coordinator()
    path = tmp_path / "in.json"
    lines = "".join('{"a": %d}\n' % i for i in range(200))
    path.write_text(lines)
    coord.execute(
        f"CREATE SOURCE fs (a int) FROM FILE '{path}' (FORMAT JSON)"
    )
    coord.configs.set("source_ingest_budget_bytes", 256)
    gid = coord.catalog.get("fs").global_id
    coord.advance()
    src = coord.file_sources[0][0]
    first = src.offset
    assert 0 < first < len(lines)  # partial ingest: the source yielded
    assert coord.overload.get("ingest_yields") >= 1
    coord.advance()
    assert src.offset > first  # later ticks drain the remainder
    # no budget: the rest arrives (up to max_records/tick), nothing lost,
    # nothing doubled
    coord.configs.set("source_ingest_budget_bytes", 0)
    coord.advance(n_rows=10_000)
    assert src.offset == len(lines)
    rows = coord.execute("SELECT count(*) FROM fs").rows
    assert rows == [(200,)]


def test_generator_rows_capped_by_budget():
    coord = Coordinator()
    coord.configs.set("source_ingest_budget_bytes", 120)
    coord.execute("CREATE SOURCE auction FROM LOAD GENERATOR AUCTION")
    coord.advance(n_rows=500)  # wants 500 bids; budget allows ~2
    rows = coord.execute("SELECT count(*) FROM bids").rows
    assert 0 < rows[0][0] <= 4
    assert coord.overload.get("ingest_yields") >= 1


def test_oversized_single_line_still_makes_progress(tmp_path):
    """Min-one-record rule: a record wider than the whole budget is consumed
    (over budget) instead of wedging the source forever."""
    from materialize_tpu.storage.file_source import FileSourceSpec, FileTailSource

    path = tmp_path / "wide.json"
    path.write_text('{"a": "%s"}\n' % ("x" * 4096))
    src = FileTailSource(
        FileSourceSpec(path=str(path), fmt="json", col_names=("a",))
    )
    records, new_off = src.poll(max_records=10, max_bytes=64)
    assert len(records) == 1 and new_off == path.stat().st_size


# -- FileBlob durability + escaping (satellites) ------------------------------


def test_fileblob_set_fsyncs_payload_and_directory(tmp_path, monkeypatch):
    from materialize_tpu.persist import FileBlob

    synced: list[int] = []
    real_fsync = os.fsync
    monkeypatch.setattr(os, "fsync", lambda fd: (synced.append(fd), real_fsync(fd))[1])
    blob = FileBlob(str(tmp_path / "blob"))
    blob.set("shard/batch-0", b"payload")
    # two fsyncs: the temp payload fd, then the directory fd (rename entry)
    assert len(synced) >= 2
    assert blob.get("shard/batch-0") == b"payload"


def test_fileblob_key_escaping_roundtrips_adversarial_keys(tmp_path):
    from materialize_tpu.persist import FileBlob

    blob = FileBlob(str(tmp_path / "blob"))
    keys = [
        "a/b",        # the normal nested key
        "a__b",       # collided with 'a/b' under the old "__" scheme
        "a%2Fb",      # literal percent-escape in the key itself
        "tmp/x",      # starts with 'tmp': invisible under the old filter
        "a/b__c/d",   # mixed
        "%",
    ]
    for i, k in enumerate(keys):
        blob.set(k, f"v{i}".encode())
    assert blob.list_keys() == sorted(keys)
    for i, k in enumerate(keys):
        assert blob.get(k) == f"v{i}".encode(), k
    # prefix listing stays key-space (not filename-space)
    assert blob.list_keys("a/") == sorted(k for k in keys if k.startswith("a/"))
    blob.delete("a/b")
    assert "a/b" not in blob.list_keys() and "a__b" in blob.list_keys()


# -- tooling ------------------------------------------------------------------


@pytest.mark.smoke
def test_listener_hygiene_check_passes():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "check_listener_hygiene.py")],
        capture_output=True,
        text=True,
    )
    assert res.returncode == 0, res.stderr


def test_listener_hygiene_check_catches_violation(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "scripts"))
    try:
        from check_listener_hygiene import check_file
    finally:
        sys.path.pop(0)
    bad = tmp_path / "bad_listener.py"
    bad.write_text(
        "import socket\n"
        "srv = socket.create_server(('127.0.0.1', 0))\n"
        "while True:\n"
        "    conn, _ = srv.accept()\n"
    )
    problems = check_file(str(bad))
    assert len(problems) == 3  # no timeout, no timeout handler, no shutdown
    good = tmp_path / "good_listener.py"
    good.write_text(
        "import socket\n"
        "srv = socket.create_server(('127.0.0.1', 0))\n"
        "srv.settimeout(0.5)\n"
        "while True:\n"
        "    try:\n"
        "        conn, _ = srv.accept()\n"
        "    except socket.timeout:\n"
        "        continue\n"
        "    except OSError:\n"
        "        break\n"
    )
    assert check_file(str(good)) == []


def test_pg_server_close_stops_accept_thread():
    """Listener hygiene in practice: close() terminates the accept thread
    even though accept() ignores listener close in this sandbox."""
    coord = Coordinator()
    srv, thread = serve_pgwire(coord, port=0)
    assert thread.is_alive()
    srv.close()
    thread.join(timeout=3.0)
    assert not thread.is_alive()
