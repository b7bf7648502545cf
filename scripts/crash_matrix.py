#!/usr/bin/env python
"""Whole-process crash-recovery matrix for the durability substrate.

The storage-plane analogue of the chaos tier (tests/test_chaos.py): a
canonical workload — tables + an upsert file source + an append-only file
source + materialized views + multi-shard txn-wal commits — runs under a
seeded `CrashPlan` (persist/crashpoints.py) that dies at exactly one
durable-op index k. The matrix sweeps k = 1..N over the durable-op trace of
a crash-free measurement run and, after every crash, restarts from the same
`data_dir` asserting:

- boot succeeds and the catalog is intact,
- the recovered logical state is byte-identical to one of the crash-free
  run's per-step snapshots — i.e. every crash lands on a statement boundary:
  either the step containing op k committed wholly or not at all,
- `persist.fsck` reports no FATAL findings,
- file sources resume EXACTLY-ONCE across the remap binding: after catch-up
  ticks, source-derived contents equal the crash-free run's final state
  (no duplicates, no gaps),
- (recovery sweep) a SECOND crash injected during `_boot` itself — txn
  apply, rehydration, MV shard reconciliation — still converges on the next
  boot, because boot is re-entrant.

Two modes: `--mode inprocess` simulates the crash with `CrashPointReached`
(BaseException: cleanup `except Exception` handlers stay cold, like a real
crash) and is fast enough for tier-1 subsets; `--mode subprocess` runs the
workload in a child process that `os._exit`s at the crash point — a genuine
whole-process crash with no unwinding at all — shipped via `MZT_CRASH_SPEC`
exactly like the network plane's `MZT_FAULT_SPEC`.

Replay: every sweep prints `CRASH_SEED=<n>`; a failing point reruns exactly
with `CRASH_SEED=<n> python scripts/crash_matrix.py --points <k>`.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

DEFAULT_SEED = 20260804

# logical relations the state dump captures (dumped only once created)
RELATIONS = ("accounts", "prices", "events", "mv_bal", "ev_counts")


def _force_cpu() -> None:
    """Child-process guard: crash-matrix children run on the CPU, never on
    an accelerator (same pinning as tests/conftest.py)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


# -- the canonical workload ---------------------------------------------------
def write_source_files(src_dir: str) -> None:
    """Deterministic external-source fixtures: an upsert keyed feed with an
    overwrite and a tombstone, and an append-only event feed."""
    os.makedirs(src_dir, exist_ok=True)
    prices = [
        {"sym": "AAA", "px": 10},
        {"sym": "BBB", "px": 20},
        {"sym": "CCC", "px": 30},
        {"sym": "AAA", "px": 11},  # overwrite
        {"sym": "BBB", "px": None},  # tombstone
        {"sym": "DDD", "px": 40},
    ]
    events = [{"id": i, "kind": "put" if i % 2 else "get"} for i in range(6)]
    with open(os.path.join(src_dir, "prices.jsonl"), "w") as f:
        f.write("".join(json.dumps(r) + "\n" for r in prices))
    with open(os.path.join(src_dir, "events.jsonl"), "w") as f:
        f.write("".join(json.dumps(r) + "\n" for r in events))


def workload_steps(src_dir: str) -> list:
    """(name, action) pairs; actions are SQL strings or coordinator closures.
    Each step is one statement/tick — the atomicity unit the matrix checks.
    Multi-shard txn commits come from advance() ticks that ingest BOTH file
    sources (+ their remap shards) in one atomic commit."""
    prices = os.path.join(src_dir, "prices.jsonl")
    events = os.path.join(src_dir, "events.jsonl")
    return [
        ("create-accounts", "CREATE TABLE accounts (id int, balance int)"),
        ("insert-accounts", "INSERT INTO accounts VALUES (1, 100), (2, 200), (3, 300)"),
        (
            "create-prices",
            f"CREATE SOURCE prices (sym text, px int) FROM FILE '{prices}' "
            "(FORMAT JSON) ENVELOPE UPSERT (KEY (sym))",
        ),
        (
            "create-events",
            f"CREATE SOURCE events (id int, kind text) FROM FILE '{events}' "
            "(FORMAT JSON)",
        ),
        (
            "create-mv-bal",
            "CREATE MATERIALIZED VIEW mv_bal AS "
            "SELECT sum(balance) AS total FROM accounts",
        ),
        (
            "create-mv-ev",
            "CREATE MATERIALIZED VIEW ev_counts AS "
            "SELECT kind, count(*) AS n FROM events GROUP BY kind",
        ),
        ("insert-late", "INSERT INTO accounts VALUES (4, 50)"),
        ("tick-1", lambda c: c.advance(2)),
        ("delete", "DELETE FROM accounts WHERE id = 2"),
        ("tick-2", lambda c: c.advance(2)),
        ("update", "UPDATE accounts SET balance = balance + 7 WHERE id = 1"),
        ("tick-3", lambda c: c.advance(4)),
        ("tick-4", lambda c: c.advance(4)),
    ]


def state_dump(coord) -> dict:
    """The workload's logical state: catalog names + sorted relation rows.
    Pure data (ints/strings), so json round-trips are byte-identical."""
    out = {
        "catalog": sorted(
            n for n, it in coord.catalog.items.items() if it.kind != "introspection"
        )
    }
    for name in RELATIONS:
        it = coord.catalog.items.get(name)
        if it is None or it.kind not in ("table", "source", "materialized_view"):
            continue
        out[name] = sorted(coord.execute(f"SELECT * FROM {name}").rows)
    return json.loads(json.dumps(out))  # tuples -> lists, like the snapshots


def empty_dump() -> dict:
    return {"catalog": []}


def run_workload(data_dir: str, src_dir: str):
    """Run the canonical workload; returns (snapshots, ops_at_step) where
    ops_at_step[i] = durable-op count after step i (from the installed
    CrashPlan; zeros when none is installed)."""
    from materialize_tpu.adapter import Coordinator
    from materialize_tpu.persist import crashpoints

    coord = Coordinator(data_dir=data_dir)
    snaps, ops_at = [], []
    for _name, action in workload_steps(src_dir):
        if isinstance(action, str):
            coord.execute(action)
        else:
            action(coord)
        snaps.append(state_dump(coord))
        plan = crashpoints.installed_plan()
        ops_at.append(plan.op_count if plan is not None else 0)
    return snaps, ops_at


def catch_up_sources(coord, max_rounds: int = 40) -> None:
    """Drive advance() until every file source has consumed its file."""
    for _ in range(max_rounds):
        srcs = getattr(coord, "file_sources", [])
        if all(
            src.offset >= os.path.getsize(src.spec.path) for src, _g, _u in srcs
        ):
            return
        coord.advance(4)


def mv_shard_divergence(coord) -> list:
    """Compare every MV's DURABLE shard against its recomputed in-memory
    collection (both encoded): the shard is what external readers (clusterd
    hydration, a future replica) see, and a crash between the base-shard
    commit and the derived persist must not leave it short a delta. Returns
    a list of 'mv gid: n rows diverged' strings (empty = consistent)."""
    import numpy as np

    from materialize_tpu.persist.shard import _consolidate_host

    problems = []
    for name, item in coord.catalog.items.items():
        if item.kind != "materialized_view":
            continue
        gid = item.global_id
        m = coord._shard(gid)
        _seq, state = m.fetch_state()
        desired = coord.storage[gid].snapshot(max(coord.oracle.read_ts(), 0))
        h = desired.to_host()
        t = np.uint64(max(int(state.upper), coord.oracle.read_ts(), 1))
        pieces = [
            {
                **{f"c{i}": c for i, c in enumerate(h["vals"])},
                "times": np.full_like(h["times"], t),
                "diffs": h["diffs"],
            }
        ]
        if state.upper > 0:
            for cols in m.snapshot(max(state.upper - 1, 0)):
                cols = dict(cols)
                cols["times"] = np.full_like(cols["times"], t)
                cols["diffs"] = -cols["diffs"]
                pieces.append(cols)
        keys = pieces[0].keys()
        merged = {k: np.concatenate([p[k] for p in pieces]) for k in keys}
        diff = _consolidate_host(merged)
        n = int(len(diff["times"]))
        if n:
            problems.append(f"{name} ({gid}): durable shard diverged by {n} rows")
    return problems


def step_of_op(ops_at: list, k: int) -> int:
    """Index of the workload step whose execution covered durable op k."""
    for i, n in enumerate(ops_at):
        if n >= k:
            return i
    return len(ops_at) - 1


# -- verification ------------------------------------------------------------
def verify_payload(data_dir: str) -> dict:
    """Boot from a (crashed) data_dir and collect every recovery fact the
    judge needs: the recovered state dump, fsck findings, MV shard
    divergence, and the post-catch-up state. Runs in-process for the
    inprocess sweep and inside the verify child for the subprocess sweep —
    ONE collection path, ONE judge (_judge_verify)."""
    from materialize_tpu.adapter import Coordinator
    from materialize_tpu.persist.fsck import fsck_data_dir

    coord = Coordinator(data_dir=data_dir)
    report = fsck_data_dir(data_dir)
    recovered = state_dump(coord)
    mv_problems = mv_shard_divergence(coord)
    catch_up_sources(coord)
    post = state_dump(coord)
    return {
        "recovered": recovered,
        "post": post,
        "mv_divergence": mv_problems,
        "fsck_fatal": [f.detail for f in report.fatal],
        "fsck_findings": [f.as_dict() for f in report.findings],
    }


def verify_recovery(data_dir: str, src_dir: str, snaps: list, ops_at: list,
                    k: int) -> dict:
    """Boot from the crashed data_dir and run the full assertion set.
    Returns a verdict dict; raises nothing (failures land in verdict)."""
    try:
        payload = verify_payload(data_dir)
    except Exception as exc:
        return {
            "k": k, "ok": False,
            "problems": [f"recovery/verification raised: {exc!r}"],
        }
    return _judge_verify(payload, snaps, ops_at, k)


# -- in-process sweep ---------------------------------------------------------
def record_run(work_dir: str, src_dir: str, seed: int):
    """Crash-free measurement run: the op trace + per-step snapshots."""
    from materialize_tpu.persist import crashpoints
    from materialize_tpu.persist.crashpoints import CrashPlan

    write_source_files(src_dir)
    record_dir = os.path.join(work_dir, "record")
    shutil.rmtree(record_dir, ignore_errors=True)  # always a fresh boot
    plan = CrashPlan(seed, crash_at=None)
    crashpoints.install(plan)
    try:
        snaps, ops_at = run_workload(record_dir, src_dir)
    finally:
        crashpoints.install(None)
    return snaps, ops_at, list(plan.trace)


def sweep_inprocess(work_dir: str, seed: int, points=None) -> list:
    from materialize_tpu.persist import crashpoints
    from materialize_tpu.persist.crashpoints import CrashPlan, CrashPointReached

    src_dir = os.path.join(work_dir, "src")
    snaps, ops_at, trace = record_run(work_dir, src_dir, seed)
    n_ops = len(trace)
    verdicts = []
    for k in points if points is not None else range(1, n_ops + 1):
        if not (1 <= k <= n_ops):
            continue
        data_dir = os.path.join(work_dir, f"crash{k}")
        shutil.rmtree(data_dir, ignore_errors=True)
        plan = CrashPlan(seed, crash_at=k)
        crashpoints.install(plan)
        crashed = None
        try:
            run_workload(data_dir, src_dir)
        except CrashPointReached as e:
            crashed = e
        finally:
            crashpoints.install(None)
        if crashed is None:
            verdicts.append(
                {"k": k, "ok": False, "problems": [f"op {k} never crashed"]}
            )
            continue
        v = verify_recovery(data_dir, src_dir, snaps, ops_at, k)
        v["label"], v["shape"] = crashed.label, crashed.shape
        verdicts.append(v)
    return verdicts


def sweep_recovery_crashes(work_dir: str, seed: int, points=None) -> list:
    """Crash-during-recovery matrix: die at a txn-wal commit point (the
    txns-shard CAS, shape=after: durable + unacked), then sweep a SECOND
    seeded crash over recovery's own durable ops; the third boot must
    converge with a clean fsck — `_boot` re-entrancy."""
    from materialize_tpu.adapter import Coordinator
    from materialize_tpu.persist import crashpoints
    from materialize_tpu.persist.crashpoints import CrashPlan, CrashPointReached
    from materialize_tpu.persist.fsck import fsck_data_dir

    src_dir = os.path.join(work_dir, "src")
    snaps, ops_at, trace = record_run(work_dir, src_dir, seed)
    txn_cas = [n for (n, label, key, _d) in trace
               if label == "cas" and key == "shard/txns"]
    if not txn_cas:
        raise RuntimeError("workload produced no txn-wal commit (bad workload)")
    k_star = txn_cas[-1]  # the last multi-shard commit: most state behind it

    crashed_dir = os.path.join(work_dir, "rc-crashed")
    shutil.rmtree(crashed_dir, ignore_errors=True)
    plan = CrashPlan(seed, crash_at=k_star, shape="after")
    crashpoints.install(plan)
    try:
        run_workload(crashed_dir, src_dir)
        raise RuntimeError(f"op {k_star} never crashed")
    except CrashPointReached:
        pass
    finally:
        crashpoints.install(None)

    # measure recovery's own durable-op count on a scratch copy
    probe_dir = os.path.join(work_dir, "rc-probe")
    shutil.rmtree(probe_dir, ignore_errors=True)
    shutil.copytree(crashed_dir, probe_dir)
    plan = CrashPlan(seed, crash_at=None)
    crashpoints.install(plan)
    try:
        Coordinator(data_dir=probe_dir)
    finally:
        crashpoints.install(None)
    m_ops = plan.op_count

    verdicts = []
    for j in points if points is not None else range(1, m_ops + 1):
        if not (1 <= j <= m_ops):
            continue
        data_dir = os.path.join(work_dir, f"rc{j}")
        shutil.rmtree(data_dir, ignore_errors=True)
        shutil.copytree(crashed_dir, data_dir)
        plan = CrashPlan(seed, crash_at=j)
        crashpoints.install(plan)
        crashed = None
        try:
            Coordinator(data_dir=data_dir)
        except CrashPointReached as e:
            crashed = e
        finally:
            crashpoints.install(None)
        v = {"k": k_star, "recovery_op": j, "ok": True, "problems": []}
        if crashed is None:
            # recovery finished before op j — only legal if recovery had
            # fewer ops than the probe (e.g. an earlier crash already
            # applied part of the work); verify convergence anyway
            v["shape"] = "none"
        else:
            v["label"], v["shape"] = crashed.label, crashed.shape
        inner = verify_recovery(data_dir, src_dir, snaps, ops_at, k_star)
        if not inner["ok"]:
            v["ok"] = False
            v["problems"] = inner["problems"]
        report = fsck_data_dir(data_dir)
        if not report.ok:
            v["ok"] = False
            v["problems"].append(
                f"fsck fatal after double-crash recovery: "
                f"{[f.detail for f in report.fatal]}"
            )
        verdicts.append(v)
    return verdicts


# -- subprocess (whole-process) sweep ----------------------------------------
def _child_env(spec: str | None) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    from materialize_tpu.persist.crashpoints import ENV_SPEC

    if spec is None:
        env.pop(ENV_SPEC, None)
    else:
        env[ENV_SPEC] = spec
    return env


def _run_child(role: str, data_dir: str, src_dir: str, out_path: str,
               spec: str | None, timeout: float = 600.0) -> int:
    cmd = [
        sys.executable, os.path.abspath(__file__), "--child", role,
        "--data-dir", data_dir, "--src-dir", src_dir, "--out", out_path,
    ]
    r = subprocess.run(
        cmd, env=_child_env(spec), cwd=REPO, timeout=timeout,
        capture_output=True, text=True,
    )
    if r.returncode not in (0, 86):
        sys.stderr.write(r.stdout[-2000:] + "\n" + r.stderr[-2000:] + "\n")
    return r.returncode


def sweep_subprocess(work_dir: str, seed: int, points=None) -> list:
    """The genuine whole-process matrix: each crash point is an os._exit in
    a child coordinator; recovery+verification runs in a second child."""
    from materialize_tpu.persist.crashpoints import CRASH_EXIT_CODE, CrashPlan

    src_dir = os.path.join(work_dir, "src")
    write_source_files(src_dir)
    # measurement child: records trace + snapshots crash-free
    trace_path = os.path.join(work_dir, "record.trace")
    out_path = os.path.join(work_dir, "record.json")
    record_dir = os.path.join(work_dir, "record")
    for stale in (trace_path, out_path):
        if os.path.exists(stale):
            os.unlink(stale)  # trace files are append-mode
    shutil.rmtree(record_dir, ignore_errors=True)
    spec = CrashPlan(seed, crash_at=None, hard=True, trace_path=trace_path).to_spec()
    rc = _run_child("workload", record_dir, src_dir, out_path, spec)
    if rc != 0:
        raise RuntimeError(f"crash-free measurement run failed (rc={rc})")
    with open(out_path) as f:
        doc = json.load(f)
    snaps, ops_at = doc["snaps"], doc["ops_at"]
    with open(trace_path) as f:
        n_ops = sum(1 for _ in f)

    verdicts = []
    for k in points if points is not None else range(1, n_ops + 1):
        if not (1 <= k <= n_ops):
            continue
        data_dir = os.path.join(work_dir, f"crash{k}")
        shutil.rmtree(data_dir, ignore_errors=True)
        k_trace = os.path.join(work_dir, f"crash{k}.trace")
        if os.path.exists(k_trace):
            os.unlink(k_trace)
        spec = CrashPlan(seed, crash_at=k, hard=True, trace_path=k_trace).to_spec()
        rc = _run_child("workload", data_dir, src_dir,
                        os.path.join(work_dir, f"crash{k}.json"), spec)
        if rc != CRASH_EXIT_CODE:
            verdicts.append({
                "k": k, "ok": False,
                "problems": [f"workload child exited {rc}, wanted crash"],
            })
            continue
        shape = "?"
        try:
            with open(k_trace) as f:
                last = f.read().strip().splitlines()[-1].split("\t")
            shape = last[3].removeprefix("crash-")
            label = last[1]
        except Exception:
            label = "?"
        vout = os.path.join(work_dir, f"verify{k}.json")
        rc = _run_child("verify", data_dir, src_dir, vout, None)
        if rc != 0:
            verdicts.append({
                "k": k, "ok": False, "label": label, "shape": shape,
                "problems": [f"verify child exited {rc}"],
            })
            continue
        with open(vout) as f:
            child = json.load(f)
        v = _judge_verify(child, snaps, ops_at, k)
        v["label"], v["shape"] = label, shape
        verdicts.append(v)
    return verdicts


def _judge_verify(payload: dict, snaps, ops_at, k: int) -> dict:
    """THE judge: every recovery assertion, applied to a verify payload
    (in-process or from a verify child) — one place to tighten."""
    verdict = {"k": k, "ok": True, "problems": [],
               "fsck_findings": payload.get("fsck_findings", [])}

    def fail(msg):
        verdict["ok"] = False
        verdict["problems"].append(msg)

    if payload["fsck_fatal"]:
        fail(f"fsck fatal: {payload['fsck_fatal']}")
    for problem in payload.get("mv_divergence", []):
        fail(f"durable MV shard inconsistent after recovery: {problem}")
    s = step_of_op(ops_at, k)
    verdict["step"] = s
    allowed = [snaps[s], snaps[s - 1] if s > 0 else empty_dump()]
    if payload["recovered"] not in allowed:
        fail(
            f"recovered state is not a statement-boundary prefix (step {s}): "
            f"{json.dumps(payload['recovered'])[:400]}"
        )
    # exactly-once resume: after catch-up ticks, source-derived contents
    # must equal the crash-free run's final state (a dup shows as extra
    # rows / wrong counts, a gap as missing rows). A crash BEFORE a
    # source's CREATE legitimately leaves it absent.
    final = snaps[-1]
    for rel in ("prices", "events", "ev_counts"):
        if rel in payload["post"] and payload["post"].get(rel) != final.get(rel):
            fail(
                f"{rel} after catch-up != crash-free final (exactly-once "
                f"violated): {payload['post'].get(rel)} vs {final.get(rel)}"
            )
    return verdict


# -- child entry points -------------------------------------------------------
def _child_workload(args) -> None:
    _force_cpu()
    from materialize_tpu.persist import crashpoints

    crashpoints.install_from_env()
    snaps, ops_at = run_workload(args.data_dir, args.src_dir)
    with open(args.out, "w") as f:
        json.dump({"snaps": snaps, "ops_at": ops_at}, f)


def _child_verify(args) -> None:
    _force_cpu()
    from materialize_tpu.persist import crashpoints

    crashpoints.install_from_env()  # set => crash-during-recovery mode
    payload = verify_payload(args.data_dir)
    with open(args.out, "w") as f:
        json.dump(payload, f)


# -- CLI ----------------------------------------------------------------------
def print_verdicts(verdicts: list, seed: int) -> None:
    print(f"CRASH_SEED={seed}")
    print(f"{'k':>4} {'op':<12} {'shape':<7} {'step':>4} verdict")
    for v in verdicts:
        k = v.get("recovery_op", v["k"])
        print(
            f"{k:>4} {v.get('label', '?'):<12} {v.get('shape', '?'):<7} "
            f"{v.get('step', -1):>4} "
            + ("PASS" if v["ok"] else "FAIL: " + "; ".join(v["problems"]))
        )
    bad = [v for v in verdicts if not v["ok"]]
    print(f"{len(verdicts) - len(bad)}/{len(verdicts)} crash points recovered")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seed", type=int,
                   default=int(os.environ.get("CRASH_SEED", DEFAULT_SEED)))
    p.add_argument("--mode", choices=("inprocess", "subprocess"),
                   default="inprocess")
    p.add_argument("--recovery", action="store_true",
                   help="sweep crash-during-recovery instead of the workload")
    p.add_argument("--points", default=None,
                   help="comma-separated crash-point indices (default: all)")
    p.add_argument("--work-dir", default=None)
    p.add_argument("--child", choices=("workload", "verify"), default=None,
                   help=argparse.SUPPRESS)
    p.add_argument("--data-dir", default=None, help=argparse.SUPPRESS)
    p.add_argument("--src-dir", default=None, help=argparse.SUPPRESS)
    p.add_argument("--out", default=None, help=argparse.SUPPRESS)
    args = p.parse_args()

    if args.child == "workload":
        _child_workload(args)
        return 0
    if args.child == "verify":
        _child_verify(args)
        return 0

    _force_cpu()
    import tempfile

    work_dir = args.work_dir or tempfile.mkdtemp(prefix="crash_matrix_")
    points = (
        [int(x) for x in args.points.split(",")] if args.points else None
    )
    if args.recovery:
        verdicts = sweep_recovery_crashes(work_dir, args.seed, points)
    elif args.mode == "subprocess":
        verdicts = sweep_subprocess(work_dir, args.seed, points)
    else:
        verdicts = sweep_inprocess(work_dir, args.seed, points)
    print_verdicts(verdicts, args.seed)
    return 0 if all(v["ok"] for v in verdicts) else 1


if __name__ == "__main__":
    sys.exit(main())
