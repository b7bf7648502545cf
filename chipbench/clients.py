"""The benchmark's clients: a raw protocol-v3 pgwire client, a SUBSCRIBE
reader on a thread of its own, and POST /api/sql over urllib.

Copied from `chip_smoke.py` (PR 25; proven on the chip) so that later PRs
cannot change what the benchmark's clients do; the subscriber reads on its
own thread and stamps each progress row with the host clock as it arrives.
"""

from __future__ import annotations

import json
import socket
import struct
import threading
import time
import urllib.error
import urllib.request


class ClientError(Exception):
    """The server answered with an error, or not at all."""

    def __init__(self, what: str, sqlstate: str = ""):
        super().__init__(what)
        self.sqlstate = sqlstate


def _sqlstate(payload: bytes) -> str:
    for field in payload.split(b"\x00"):
        if field[:1] == b"C":
            return field[1:].decode()
    return ""


class PgClient:
    def __init__(self, port: int, timeout: float = 1150.0):  # a cold CREATE MATERIALIZED VIEW compiles for over 600 s
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=timeout)
        self.sock.sendall(struct.pack(">II", 8, 80877103))  # SSLRequest
        if self.sock.recv(1) != b"N":
            raise ClientError("SSLRequest not answered with N")
        params = b"user\x00chipbench\x00database\x00materialize\x00\x00"
        payload = struct.pack(">I", 196608) + params
        self.sock.sendall(struct.pack(">I", len(payload) + 4) + payload)
        if not any(t == b"R" for t, _ in self.read_until(b"Z")):
            raise ClientError("no AuthenticationOk")

    def _read_exact(self, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            if not chunk:
                raise ClientError("server hung up")
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
        """Simple query; returns text rows once the last has arrived."""
        self.send_query(sql)
        rows, error = [], None
        for t, p in self.read_until(b"Z"):
            if t == b"E":
                error = ClientError(f"{' '.join(sql.split()[:3])}: {p!r}", _sqlstate(p))
            elif t == b"D":
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
        if error is not None:
            raise error
        return rows

    def close(self) -> None:
        try:
            self.sock.sendall(b"X" + struct.pack(">I", 4))
        except OSError:
            pass
        self.sock.close()


class Subscriber:
    """`SUBSCRIBE <view> WITH (PROGRESS)` on a connection and a thread of its
    own. Consolidates the diffs per row payload and stamps every progress row
    (frontier, host clock) as it arrives, so a freshness sample never waits
    for the loop that issues refreshes."""

    def __init__(self, port: int, view: str):
        self.client = PgClient(port)
        self.client.send_query(f"SUBSCRIBE {view} WITH (PROGRESS)")
        tag, payload = self.client.read_message()
        if tag != b"H":
            raise ClientError(f"expected CopyOutResponse, got {tag!r} {payload!r}", _sqlstate(payload))
        self.agg: dict = {}
        self.frontier = 0
        self.stamps: list = []  # (frontier, perf_counter) per progress row
        self.error: Exception | None = None
        self._cond = threading.Condition()
        self._stop = False
        self._thread = threading.Thread(target=self._read, name="chipbench-subscriber", daemon=True)
        self._thread.start()

    def _read(self) -> None:
        try:
            while True:
                tag, p = self.client.read_message()
                if tag != b"d":
                    if self._stop and tag in (b"c", b"C", b"Z"):
                        if tag == b"Z":
                            return
                        continue
                    raise ClientError(f"unexpected message {tag!r} mid-stream: {p!r}", _sqlstate(p))
                f = p.decode().rstrip("\n").split("\t")
                if f[1] == "t":
                    now = time.perf_counter()
                    with self._cond:
                        self.frontier = max(self.frontier, int(f[0]))
                        self.stamps.append((self.frontier, now))
                        self._cond.notify_all()
                else:
                    cols = tuple(f[3:])
                    n = self.agg.get(cols, 0) + int(f[2])
                    if n:
                        self.agg[cols] = n
                    else:
                        del self.agg[cols]
        except Exception as e:  # the reader's boundary: hand the failure to whoever waits
            with self._cond:
                self.error = e
                self._cond.notify_all()

    def wait_past(self, ts: int, timeout: float) -> float | None:
        """Blocks until a progress row says every update at times <= ts has
        been delivered; returns the host clock of that row, or None when it
        did not come in `timeout` seconds or the stream broke."""
        deadline = time.perf_counter() + timeout
        with self._cond:
            while self.frontier <= ts and self.error is None:
                left = deadline - time.perf_counter()
                if left <= 0:
                    return None
                self._cond.wait(left)
            return self.arrival(ts)

    def arrival(self, ts: int) -> float | None:
        """Host clock of the first progress row past `ts`, if one has come."""
        for frontier, at in self.stamps:
            if frontier > ts:
                return at
        return None

    def rows(self) -> dict:
        """The consolidated rows so far: {column texts: multiplicity}."""
        with self._cond:
            return dict(self.agg)

    def close(self) -> None:
        self._stop = True
        try:
            self.client.sock.sendall(b"H" + struct.pack(">I", 4))  # Flush ends the stream
        except OSError:
            pass
        self._thread.join(timeout=30)
        self.client.close()


def http_sql(port: int, sql: str, timeout: float = 600.0) -> list:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/sql",
        data=json.dumps({"query": sql}).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            doc = json.loads(resp.read())
    except urllib.error.HTTPError as e:
        body = e.read().decode(errors="replace")
        code = ""
        try:
            code = json.loads(body).get("code", "")
        except ValueError:
            pass
        raise ClientError(f"POST /api/sql -> {e.code}: {body[:200]}", code) from e
    return doc["results"][-1]["rows"]
