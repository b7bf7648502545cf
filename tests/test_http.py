"""HTTP frontend: SQL over HTTP, SUBSCRIBE long-poll, metrics endpoint."""

import json
import threading
import time
import tracemalloc
import urllib.request

import pytest

from materialize_tpu.adapter import Coordinator
from materialize_tpu.frontend import serve


@pytest.fixture
def server():
    coord = Coordinator()
    httpd = serve(coord, port=0)  # ephemeral port
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    yield base, coord
    httpd.shutdown()


def post(base, path, doc):
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


def get(base, path):
    with urllib.request.urlopen(base + path) as r:
        body = r.read()
        try:
            return json.loads(body), r.status
        except json.JSONDecodeError:
            return body.decode(), r.status


def test_sql_over_http(server):
    base, _ = server
    doc, status = post(base, "/api/sql", {"query": "CREATE TABLE t (a int); INSERT INTO t VALUES (1), (2); SELECT a FROM t ORDER BY a"})
    assert status == 200
    assert doc["results"][0]["ok"].startswith("CREATE")
    assert doc["results"][2]["rows"] == [[1], [2]]
    assert doc["results"][2]["col_names"] == ["a"]


def test_sql_error_reported(server):
    base, _ = server
    doc, status = post(base, "/api/sql", {"query": "SELECT oops FROM nowhere"})
    assert status == 400 and "error" in doc


def test_subscribe_poll(server):
    base, _ = server
    post(base, "/api/sql", {"query": "CREATE TABLE t (a int)"})
    post(base, "/api/sql", {"query": "CREATE MATERIALIZED VIEW mv AS SELECT a, count(*) AS n FROM t GROUP BY a"})
    doc, status = post(base, "/api/subscribe", {"query": "SUBSCRIBE mv"})
    assert status == 200
    sub = doc["subscription_id"]
    post(base, "/api/sql", {"query": "INSERT INTO t VALUES (5)"})
    doc, _ = get(base, f"/api/subscribe/{sub}/poll")
    assert {"row": [5, 1], "timestamp": doc["updates"][0]["timestamp"], "diff": 1} in doc["updates"]
    # second poll: no new updates
    post(base, "/api/sql", {"query": "INSERT INTO t VALUES (5)"})
    doc2, _ = get(base, f"/api/subscribe/{sub}/poll")
    diffs = [(u["row"][1], u["diff"]) for u in doc2["updates"]]
    assert (1, -1) in diffs and (2, 1) in diffs  # count 1 retracted, 2 asserted


def test_readyz_and_metrics(server):
    base, _ = server
    body, status = get(base, "/api/readyz")
    assert status == 200
    post(base, "/api/sql", {"query": "CREATE TABLE t (a int)"})
    body, status = get(base, "/metrics")
    assert status == 200
    assert "mzt_catalog_items" in body


def test_prof_endpoints(server):
    """mz-prof analogue: sampling CPU profile (folded stacks) + heap top."""
    base, coord = server
    # background work so the sampler has something to see
    stop = threading.Event()

    def busy():
        while not stop.is_set():
            sum(i * i for i in range(2000))
            time.sleep(0.001)

    t = threading.Thread(target=busy, daemon=True)
    t.start()
    body = urllib.request.urlopen(
        f"{base}/prof/cpu?seconds=0.3", timeout=30
    ).read().decode()
    stop.set()
    assert "samples over" in body
    assert ";" in body or "distinct stacks" in body
    try:
        h1 = urllib.request.urlopen(f"{base}/prof/heap", timeout=30).read().decode()
        assert "tracemalloc" in h1
        coord.execute("CREATE TABLE ph (a int)")
        coord.execute("INSERT INTO ph VALUES (1), (2)")
        h2 = urllib.request.urlopen(f"{base}/prof/heap", timeout=30).read().decode()
        assert "KiB" in h2
    finally:
        # /prof/heap started it, and a server never stops it (conftest.py)
        tracemalloc.stop()
