"""Tier-1 wiring + unit fixtures for mzlint (materialize_tpu/analysis).

Every registered pass gets a paired positive/negative fixture (the
positive MUST flag, the negative MUST stay silent), the suppression
machinery gets a full round-trip (used allow silences; unused and
unknown allows are themselves findings), and the whole repo must come
back clean — `test_repo_is_clean`/`test_cli_all_exits_zero` are the CI
gate the ISSUE asks for: any new finding fails tier-1.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from materialize_tpu.analysis import (  # noqa: E402
    ALL_RULES,
    RULES_BY_ID,
    Project,
    SourceFile,
    load_project,
    run_rules,
)
from materialize_tpu.analysis.core import UNUSED_SUPPRESSION  # noqa: E402


def proj(**files) -> Project:
    """Synthetic in-memory project: keyword 'a__b__c' -> rel 'a/b/c.py'."""
    sfs = [
        SourceFile(rel.replace("__", "/") + ".py", textwrap.dedent(src))
        for rel, src in files.items()
    ]
    return Project(sfs)


def run(project, *rule_ids, known=None):
    rules = [RULES_BY_ID[r] for r in rule_ids]
    return run_rules(project, rules, known_ids=known)


# -- lock-discipline ----------------------------------------------------------

RACY = """
    import threading

    class Stats:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0

        def start(self):
            threading.Thread(target=self._worker, daemon=True).start()

        def _worker(self):
            with self._lock:
                self.count += 1

        def read(self):
            return self.count
"""


def test_lock_discipline_flags_unguarded_cross_thread_read():
    fs = run(proj(materialize_tpu__cluster__fix=RACY), "lock-discipline")
    assert len(fs) == 1 and "count" in fs[0].message, fs


def test_lock_discipline_quiet_when_read_is_guarded():
    fixed = RACY.replace(
        "            return self.count",
        "            with self._lock:\n                return self.count",
    )
    assert not run(proj(materialize_tpu__cluster__fix=fixed), "lock-discipline")


def test_lock_discipline_honors_locked_suffix_convention():
    src = RACY.replace("def read(self):", "def _read_locked(self):").replace(
        "        def _worker", "        def read(self):\n"
        "            with self._lock:\n"
        "                return self._read_locked()\n\n"
        "        def _worker"
    )
    assert not run(proj(materialize_tpu__cluster__fix=src), "lock-discipline")


def test_lock_discipline_ignores_init_and_single_root():
    src = """
        import threading

        class OneThread:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            def bump(self):
                with self._lock:
                    self.n += 1

            def read(self):
                return self.n
    """
    # no thread root at all: external-only access is not a race
    assert not run(proj(materialize_tpu__cluster__one=src), "lock-discipline")


# -- blocking-under-lock ------------------------------------------------------

SLEEPY = """
    import threading
    import time

    class Gate:
        def __init__(self):
            self._lock = threading.Lock()

        def wait(self):
            with self._lock:
                time.sleep(1.0)
"""


def test_blocking_under_lock_flags_sleep():
    fs = run(proj(materialize_tpu__cluster__gate=SLEEPY), "blocking-under-lock")
    assert len(fs) == 1 and "time.sleep" in fs[0].message, fs


def test_blocking_under_lock_quiet_outside_lock():
    src = SLEEPY.replace(
        "            with self._lock:\n                time.sleep(1.0)",
        "            with self._lock:\n                pass\n"
        "            time.sleep(1.0)",
    )
    assert not run(proj(materialize_tpu__cluster__gate=src), "blocking-under-lock")


def test_blocking_under_lock_flags_frame_io_and_resets_in_nested_def():
    src = """
        import threading

        class Client:
            def __init__(self):
                self._lock = threading.Lock()

            def rpc(self, sock, frame):
                with self._lock:
                    send_frame(sock, frame)       # flagged
                    def later():
                        recv_frame(sock)          # deferred: NOT flagged
                    return later
    """
    fs = run(proj(materialize_tpu__cluster__cl=src), "blocking-under-lock")
    assert len(fs) == 1 and "send_frame" in fs[0].message, fs


# -- crash-swallow ------------------------------------------------------------


def test_crash_swallow_flags_baseexception_without_reraise():
    src = """
        def run(step):
            try:
                step()
            except BaseException:
                pass
    """
    fs = run(proj(materialize_tpu__persist__x=src), "crash-swallow")
    assert len(fs) == 1, fs


def test_crash_swallow_allows_cleanup_then_reraise():
    src = """
        def run(step, undo):
            try:
                step()
            except BaseException:
                undo()
                raise
    """
    assert not run(proj(materialize_tpu__persist__x=src), "crash-swallow")


# -- durable-cleanup ----------------------------------------------------------


def test_durable_cleanup_flags_blob_op_in_handler():
    src = """
        def write(blob, key):
            try:
                blob.set(key, b"v")
            except Exception:
                blob.delete(key)
                raise
    """
    fs = run(proj(materialize_tpu__persist__w=src), "durable-cleanup")
    assert len(fs) == 1 and "delete" in fs[0].message, fs


def test_durable_cleanup_quiet_for_non_durable_receivers():
    src = """
        def write(cache, key):
            try:
                cache.set(key, b"v")
            except Exception:
                cache.delete(key)
                raise
    """
    assert not run(proj(materialize_tpu__persist__w=src), "durable-cleanup")


# -- tracer safety ------------------------------------------------------------


def test_traced_coercion_flags_if_on_jitted_param():
    src = """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """
    fs = run(proj(materialize_tpu__ops__fix=src), "traced-coercion")
    assert len(fs) == 1 and "`if`" in fs[0].message, fs


def test_traced_coercion_exempts_static_args_and_identity_checks():
    src = """
        from functools import partial

        import jax
        import jax.numpy as jnp

        @partial(jax.jit, static_argnames=("n",))
        def f(x, n, since=None):
            if n > 3:                 # static: host int
                x = x + 1
            if since is not None:     # identity check: host-decidable
                x = x + since
            return jnp.where(x > 0, x, -x)
    """
    assert not run(proj(materialize_tpu__ops__fix=src), "traced-coercion")


def test_traced_coercion_nested_helper_params_not_assumed_traced():
    src = """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x, specs):
            def scale(col, s):
                if not s:             # host int bound at the call site
                    return col
                return col * s
            return scale(x, 2)
    """
    assert not run(proj(materialize_tpu__ops__fix=src), "traced-coercion")


def test_traced_np_call_flags_host_pull():
    src = """
        import jax.numpy as jnp
        import numpy as np

        def f(xs):
            y = jnp.cumsum(xs)
            return np.sum(y)
    """
    fs = run(proj(materialize_tpu__ops__fix=src), "traced-np-call")
    assert len(fs) == 1 and "np.sum" in fs[0].message, fs


def test_traced_np_call_quiet_on_host_literals():
    src = """
        import numpy as np

        def f(n):
            return np.zeros((n,), dtype=np.float32)
    """
    assert not run(proj(materialize_tpu__ops__fix=src), "traced-np-call")


def test_traced_searchsorted_banned_in_scope_only():
    src = "import jax.numpy as jnp\n\n\ndef f(a, v):\n    return jnp.searchsorted(a, v)\n"
    assert run(proj(materialize_tpu__ops__bad=src), "traced-searchsorted")
    # out of scope (host-side adapter code): allowed
    assert not run(proj(materialize_tpu__adapter__ok=src), "traced-searchsorted")


# -- dtype-64bit --------------------------------------------------------------


def test_dtype64_flags_hot_path_64bit():
    src = "import jax.numpy as jnp\n\nx = jnp.zeros((4,), dtype=jnp.uint64)\n"
    fs = run(proj(materialize_tpu__ops__k=src), "dtype-64bit")
    assert len(fs) == 1, fs


def test_dtype64_ignores_comments():
    src = "import jax.numpy as jnp\n\nx = 1  # jnp.uint64 would cost 2x here\n"
    assert not run(proj(materialize_tpu__ops__k=src), "dtype-64bit")


# -- listener-hygiene ---------------------------------------------------------

BAD_LISTENER = """
    import socket

    def serve(srv):
        while True:
            conn, _ = srv.accept()
"""

GOOD_LISTENER = """
    import socket

    def serve(srv):
        srv.settimeout(0.5)
        while True:
            try:
                conn, _ = srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
"""


def test_listener_hygiene_flags_all_three_needles():
    fs = run(proj(materialize_tpu__frontend__l=BAD_LISTENER), "listener-hygiene")
    assert len(fs) == 3, fs


def test_listener_hygiene_quiet_on_compliant_loop():
    assert not run(
        proj(materialize_tpu__frontend__l=GOOD_LISTENER), "listener-hygiene"
    )


# -- registry coherence -------------------------------------------------------

DYNCFG_DECL = """
    class Config:
        def __init__(self, name, default, desc):
            self.name = name

    USED = Config("used_cfg", 1, "d")
    ORPHAN = Config("orphan_cfg", 2, "d")
"""


def test_dyncfg_coherence_flags_orphans_both_ways():
    reader = 'v = configs.get("used_cfg")\nw = configs.get("ghost_cfg")\n'
    fs = run(
        proj(
            materialize_tpu__adapter__dyncfg=DYNCFG_DECL,
            materialize_tpu__adapter__reader=reader,
        ),
        "dyncfg-coherence",
    )
    msgs = "\n".join(f.message for f in fs)
    assert len(fs) == 2 and "ghost_cfg" in msgs and "orphan_cfg" in msgs, fs


def test_dyncfg_coherence_quiet_when_matched():
    reader = (
        'v = configs.get("used_cfg")\n'
        'w = cfg["orphan_cfg"]\n'  # subscript read counts too
    )
    assert not run(
        proj(
            materialize_tpu__adapter__dyncfg=DYNCFG_DECL,
            materialize_tpu__adapter__reader=reader,
        ),
        "dyncfg-coherence",
    )


ERRORS_SRC = """
    class SqlError(Exception):
        sqlstate = "XX000"

    class QueryCanceled(SqlError):
        sqlstate = "57014"
"""


def test_sqlstate_coherence_flags_unknown_wire_literal():
    fe = '_send_error("99999", "boom")\n_send_error("57014", "ok")\n'
    fs = run(
        proj(
            materialize_tpu__errors=ERRORS_SRC,
            materialize_tpu__frontend__pg=fe,
        ),
        "sqlstate-coherence",
    )
    assert len(fs) == 1 and "99999" in fs[0].message, fs


def test_sqlstate_coherence_flags_malformed_class_state():
    bad = (
        textwrap.dedent(ERRORS_SRC)
        + '\n\nclass Oops(SqlError):\n    sqlstate = "XYZ"\n'
    )
    fs = run(proj(materialize_tpu__errors=bad), "sqlstate-coherence")
    assert len(fs) == 1 and "Oops" in fs[0].message, fs


PROTO_SRC = """
    from dataclasses import dataclass

    @dataclass(frozen=True)
    class Ping:
        pass

    @dataclass(frozen=True)
    class Pong:
        pass

    @dataclass(frozen=True)
    class Dead:
        pass
"""


def test_ctp_coherence_flags_unhandled_and_dead_frames():
    ctl = "import protocol as p\n\nr = send(p.Ping())\n"
    cld = "import protocol as p\n\nreply = p.Pong()\n"
    fs = run(
        proj(
            materialize_tpu__cluster__protocol=PROTO_SRC,
            materialize_tpu__cluster__controller=ctl,
            materialize_tpu__cluster__clusterd=cld,
        ),
        "ctp-coherence",
    )
    msgs = "\n".join(f.message for f in fs)
    assert len(fs) == 3, fs
    assert "'Ping'" in msgs and "'Pong'" in msgs and "'Dead'" in msgs


def test_ctp_coherence_quiet_when_dispatched():
    ctl = (
        "import protocol as p\n\n"
        "r = send(p.Ping())\n"
        "assert isinstance(r, p.Pong)\n"
        "d = handle(p.Dead())\n"
    )
    cld = (
        "import protocol as p\n\n"
        "def dispatch(cmd):\n"
        "    if isinstance(cmd, (p.Ping, p.Dead)):\n"
        "        return p.Pong()\n"
    )
    assert not run(
        proj(
            materialize_tpu__cluster__protocol=PROTO_SRC,
            materialize_tpu__cluster__controller=ctl,
            materialize_tpu__cluster__clusterd=cld,
        ),
        "ctp-coherence",
    )


# -- collective-coherence ------------------------------------------------------

MESH_DEF = """
    WORKERS = "workers"
"""

PLANE_OK = """
    from jax import lax

    def exchange(buckets):
        return lax.all_to_all(buckets, "workers", 0, 0)

    def fold(x):
        return lax.psum(x, axis_name="workers")
"""


def test_collective_coherence_quiet_inside_plane_with_declared_axis():
    assert not run(
        proj(
            materialize_tpu__parallel__mesh=MESH_DEF,
            materialize_tpu__parallel__devicemesh__exchange=PLANE_OK,
        ),
        "collective-coherence",
    )


def test_collective_coherence_flags_collective_outside_plane():
    fs = run(
        proj(
            materialize_tpu__parallel__mesh=MESH_DEF,
            materialize_tpu__dataflow__rogue=PLANE_OK,
        ),
        "collective-coherence",
    )
    assert len(fs) == 2 and all("outside" in f.message for f in fs), fs


def test_collective_coherence_flags_axis_literal_mismatch():
    src = PLANE_OK.replace('axis_name="workers"', 'axis_name="shards"')
    fs = run(
        proj(
            materialize_tpu__parallel__mesh=MESH_DEF,
            materialize_tpu__parallel__devicemesh__exchange=src,
        ),
        "collective-coherence",
    )
    assert len(fs) == 1 and "'shards'" in fs[0].message, fs


def test_collective_coherence_follows_the_mesh_definition():
    # the declared axis is read FROM parallel/mesh.py, not hardcoded: rename
    # the axis everywhere and the same sources stay clean
    fs = run(
        proj(
            materialize_tpu__parallel__mesh=MESH_DEF.replace("workers", "shards"),
            materialize_tpu__parallel__devicemesh__exchange=PLANE_OK.replace(
                "workers", "shards"
            ),
        ),
        "collective-coherence",
    )
    assert not fs, fs


def test_collective_coherence_flags_host_pulls_in_plane_functions():
    src = """
        import numpy as np
        from jax.experimental import io_callback

        TABLE = np.zeros(4)  # module-level config: allowed

        def exchange(buckets):
            counts = np.asarray(buckets)
            io_callback(print, None, buckets)
            return counts
    """
    fs = run(
        proj(
            materialize_tpu__parallel__mesh=MESH_DEF,
            materialize_tpu__parallel__devicemesh__exchange=src,
        ),
        "collective-coherence",
    )
    msgs = " | ".join(f.message for f in fs)
    assert len(fs) == 2 and "np.asarray" in msgs and "io_callback" in msgs, fs


# -- reactor-discipline -------------------------------------------------------

def test_reactor_discipline_flags_blocking_calls_on_the_loop():
    src = """
        import time

        class Server:
            def _conn_event(self, c, mask):
                c.sock.sendall(b"x")
                time.sleep(0.1)
                with self.lock:
                    self.coord.tick()
    """
    fs = run(proj(materialize_tpu__serve__bad=src), "reactor-discipline")
    msgs = " | ".join(f.message for f in fs)
    assert len(fs) == 3, fs
    assert "sendall" in msgs and "time.sleep" in msgs and "with lock" in msgs.replace("'with lock:'", "with lock"), msgs


def test_reactor_discipline_flags_recv_outside_readiness_handler():
    src = """
        class Server:
            def _pump(self, c):
                return c.sock.recv(4096)

            def _conn_readable(self, c, mask):
                return c.sock.recv(4096)
    """
    fs = run(proj(materialize_tpu__serve__bad=src), "reactor-discipline")
    assert len(fs) == 1 and "readiness" in fs[0].message, fs


def test_reactor_discipline_requires_nonblocking_sockets():
    src = """
        import socket

        class Server:
            def __init__(self, host, port):
                self.srv = socket.create_server((host, port))

            def _listener_readable(self, sock, mask):
                c, _ = sock.accept()
                c.setblocking(True)
    """
    fs = run(proj(materialize_tpu__serve__bad=src), "reactor-discipline")
    msgs = " | ".join(f.message for f in fs)
    assert len(fs) == 3, fs  # two never-nonblocking fns + setblocking(True)
    assert "setblocking(False)" in msgs and "setblocking(True)" in msgs, msgs


def test_reactor_discipline_quiet_on_disciplined_reactor():
    src = """
        import socket
        import threading

        class Server:
            def __init__(self, host, port):
                self._mutex = threading.Lock()
                self.srv = socket.create_server((host, port))
                self.srv.setblocking(False)

            def _listener_readable(self, sock, mask):
                while True:
                    try:
                        c, _ = sock.accept()
                    except BlockingIOError:
                        return
                    c.setblocking(False)

            def _conn_readable(self, c, mask):
                data = c.sock.recv(65536)
                with self._mutex:
                    self.nbytes += len(data)

            def _job_done(self, c, result, exc):
                self.reactor.submit(lambda: self.dispatch(c), self._job_done)
    """
    fs = run(proj(materialize_tpu__serve__good=src), "reactor-discipline")
    assert not fs, fs


def test_reactor_discipline_scoped_to_serve_only():
    src = """
        class Handler:
            def handle(self):
                self.sock.sendall(b"x")
                with self.lock:
                    self.coord.tick()
    """
    fs = run(proj(materialize_tpu__frontend__h=src), "reactor-discipline")
    assert not fs, fs


def test_listener_hygiene_exempts_nonblocking_readiness_accept():
    src = """
        def _listener_readable(sock, mask):
            while True:
                try:
                    c, _ = sock.accept()
                except BlockingIOError:
                    return
                c.setblocking(False)
    """
    fs = run(proj(materialize_tpu__serve__loop=src), "listener-hygiene")
    assert not fs, fs


# -- suppressions -------------------------------------------------------------


def test_trailing_allow_suppresses_and_counts_as_used():
    src = SLEEPY.replace(
        "time.sleep(1.0)",
        "time.sleep(1.0)  # mzt: allow(blocking-under-lock)",
    )
    assert not run(proj(materialize_tpu__cluster__gate=src), "blocking-under-lock")


def test_standalone_allow_covers_next_line():
    src = SLEEPY.replace(
        "                time.sleep(1.0)",
        "                # mzt: allow(blocking-under-lock)\n"
        "                time.sleep(1.0)",
    )
    assert not run(proj(materialize_tpu__cluster__gate=src), "blocking-under-lock")


def test_unused_allow_is_a_finding():
    src = "x = 1  # mzt: allow(blocking-under-lock)\n"
    fs = run(proj(materialize_tpu__cluster__g=src), "blocking-under-lock")
    assert len(fs) == 1 and fs[0].rule == UNUSED_SUPPRESSION, fs
    assert "suppresses nothing" in fs[0].message


def test_unknown_allow_id_is_a_finding_even_for_unrun_rules():
    src = "x = 1  # mzt: allow(not-a-rule)\n"
    fs = run(
        proj(materialize_tpu__cluster__g=src),
        "dtype-64bit",
        known=set(RULES_BY_ID),
    )
    assert len(fs) == 1 and "unknown rule id" in fs[0].message, fs


def test_allow_for_unrun_rule_is_not_reported_unused():
    # the allow targets a KNOWN rule that simply wasn't part of this run:
    # it must neither suppress nor be called unused
    src = "x = 1  # mzt: allow(blocking-under-lock)\n"
    fs = run(
        proj(materialize_tpu__cluster__g=src),
        "dtype-64bit",
        known=set(RULES_BY_ID),
    )
    assert not fs, fs


# -- the CI gate: whole repo is clean -----------------------------------------


def test_repo_is_clean_under_every_ast_rule():
    project = load_project()
    rules = [r for r in ALL_RULES if not r.functional]
    fs = run_rules(project, rules, known_ids=set(RULES_BY_ID))
    assert not fs, "\n".join(f.render() for f in fs)


def test_cli_all_exits_zero():
    r = subprocess.run(
        [sys.executable, "-m", "materialize_tpu.analysis", "--all", "--json"],
        capture_output=True,
        text=True,
        timeout=300,
        cwd=str(REPO),
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin:/usr/local/bin"},
    )
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert payload["findings"] == []
    assert "metrics-coherence" in payload["rules"]


def test_cli_json_is_stable_and_machine_readable():
    args = [
        sys.executable, "-m", "materialize_tpu.analysis",
        "--rules", "dtype-64bit,listener-hygiene", "--json",
    ]
    runs = [
        subprocess.run(
            args, capture_output=True, text=True, timeout=120, cwd=str(REPO)
        )
        for _ in range(2)
    ]
    assert runs[0].returncode == 0 and runs[0].stdout == runs[1].stdout
    payload = json.loads(runs[0].stdout)
    assert set(payload) == {"rules", "files", "findings"}


def test_cli_rejects_unknown_rule_id():
    r = subprocess.run(
        [sys.executable, "-m", "materialize_tpu.analysis", "--rules", "bogus"],
        capture_output=True,
        text=True,
        timeout=120,
        cwd=str(REPO),
    )
    assert r.returncode == 2 and "unknown rule id" in r.stderr


def test_cli_list_names_every_registered_rule():
    r = subprocess.run(
        [sys.executable, "-m", "materialize_tpu.analysis", "--list"],
        capture_output=True,
        text=True,
        timeout=120,
        cwd=str(REPO),
    )
    assert r.returncode == 0
    for rule in ALL_RULES:
        assert rule.id in r.stdout
