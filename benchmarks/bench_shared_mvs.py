"""Multi-MV arrangement-sharing benchmark (PR 9).

Installs K identical-source MVs (the same two-table join) and measures
per-tick wall time and total arrangement bytes with the TraceManager enabled
vs force-disabled (`enable_arrangement_sharing`). The sharing contract says
per-tick arrangement maintenance is ~O(sources), not O(K × sources): the
8-MV shared tick should sit well under the 8× of the private path, and the
input arrangements should be held ONCE regardless of K.

Honest labeling (the bench.py rules): metrics are suffixed `_cpu_fallback`
unless the backend is a real TPU — absolute numbers from the XLA:CPU
fallback say nothing about TPU wall time; the shared-vs-private RATIOS at a
fixed K are the contract.

Usage:
  MZT_BENCH_CPU=1 python -m benchmarks.bench_shared_mvs \
      [--rows 3000] [--ticks 8] [--out benchmarks/shared_mvs_cpu_r9.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _maybe_cpu():
    if os.environ.get("MZT_BENCH_CPU") == "1":
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")


def _platform_suffix() -> str:
    import jax

    return "" if jax.devices()[0].platform == "tpu" else "_cpu_fallback"


# -- arrangement accounting ---------------------------------------------------


def _batch_bytes(b) -> int:
    n = 0
    for attr in ("hashes", "times", "diffs"):
        v = getattr(b, attr, None)
        if v is not None:
            n += int(getattr(v, "nbytes", 0))
    for attr in ("keys", "vals"):
        for col in getattr(b, attr, ()) or ():
            n += int(getattr(col, "nbytes", 0))
    return n


def _state_objects(coord):
    """Every distinct arrangement-bearing object across installed dataflows,
    deduped by identity — a trace shared by N readers is counted ONCE, a
    private copy per reader N times. That asymmetry IS the metric."""
    from materialize_tpu.dataflow.runtime import (
        ArrangeByNode,
        DeltaJoinNode,
        LinearJoinNode,
        ReduceNode,
        SharedArrangeNode,
        SharedReduceNode,
    )

    seen: dict[int, object] = {}

    def add(obj):
        if obj is not None:
            seen[id(obj)] = obj

    for _gid, df, _src in coord.dataflows:
        for _obj, steps, _out in getattr(df, "builds", []):
            for node, _refs in steps:
                if isinstance(node, ArrangeByNode):
                    add(node.arr)
                elif isinstance(node, SharedArrangeNode):
                    add(node.h.trace.arr)
                elif isinstance(node, LinearJoinNode):
                    for left, right in node.state:
                        add(left)
                        add(right)
                    for lh, rh in node.shared:
                        for h in (lh, rh):
                            if h is not None:
                                add(h.trace.arr)
                elif isinstance(node, DeltaJoinNode):
                    for arr in node.arrs.values():
                        add(arr)
                    for h in node.shared.values():
                        add(h.trace.arr)
                elif isinstance(node, ReduceNode):
                    add(node.state)
                elif isinstance(node, SharedReduceNode):
                    add(node.h.trace.state)
                    add(node.h.trace.out_arr)
        for arr in list(getattr(df, "index_traces", {}).values()) + list(
            getattr(df, "index_errs", {}).values()
        ):
            add(arr)
    return list(seen.values())


def arrangement_bytes(coord) -> int:
    total = 0
    for obj in _state_objects(coord):
        batches = getattr(obj, "batches", None)
        if batches is not None:  # Arrangement
            total += sum(_batch_bytes(b) for b in batches)
        else:  # AccumState and friends: sum its array leaves
            for attr in ("hashes", "times"):
                v = getattr(obj, attr, None)
                if v is not None:
                    total += int(getattr(v, "nbytes", 0))
            for attr in ("keys", "accums", "vals"):
                for col in getattr(obj, attr, ()) or ():
                    total += int(getattr(col, "nbytes", 0))
    return total


# -- the workload -------------------------------------------------------------

_Q = "SELECT t1.k AS k, a, b FROM t1, t2 WHERE t1.k = t2.k"


def run_scenario(k: int, sharing: bool, rows: int = 3000, ticks: int = 8):
    """Returns dict(tick_wall_s_median, arrangement_bytes, imports, exports).

    t1 keys [0, rows), t2 keys [rows-50, 2*rows-50): a ~50-key overlap keeps
    the join OUTPUT small while both INPUT arrangements are `rows` deep —
    the regime where per-reader arrangement maintenance dominates and
    sharing pays (selective joins over wide sources, the delta-join premise).
    Churn ticks append mostly non-matching keys plus a few matches and a
    delete, so spine merges keep firing.
    """
    from materialize_tpu.adapter import Coordinator

    c = Coordinator()
    if not sharing:
        c.execute("ALTER SYSTEM SET enable_arrangement_sharing = false")
    c.execute("CREATE TABLE t1 (k int, a int)")
    c.execute("CREATE TABLE t2 (k int, b int)")
    for lo in range(0, rows, 1000):
        hi = min(lo + 1000, rows)
        c.execute(
            "INSERT INTO t1 VALUES "
            + ", ".join(f"({i}, {i % 97})" for i in range(lo, hi))
        )
        c.execute(
            "INSERT INTO t2 VALUES "
            + ", ".join(f"({i + rows - 50}, {i % 89})" for i in range(lo, hi))
        )
    for i in range(k):
        c.execute(f"CREATE MATERIALIZED VIEW bench_mv_{i} AS {_Q}")
    # one warmup churn tick (compile paths, first spine merges)
    c.execute(f"INSERT INTO t1 VALUES ({2 * rows}, 1), ({rows - 1}, 2)")
    walls = []
    nxt = 2 * rows + 1
    for t in range(ticks):
        stmts = [
            "INSERT INTO t1 VALUES "
            + ", ".join(f"({nxt + j}, {j})" for j in range(40))
            + f", ({rows - 2 - t}, 7)",  # one matching key
            "INSERT INTO t2 VALUES "
            + ", ".join(f"({nxt + 400000 + j}, {j})" for j in range(40))
            + f", ({rows + t}, 9)",
            f"DELETE FROM t1 WHERE k = {nxt + 3}",
        ]
        nxt += 50
        t0 = time.perf_counter()
        for s in stmts:
            c.execute(s)
        walls.append((time.perf_counter() - t0) / len(stmts))
    walls.sort()
    tm = c.trace_manager
    return {
        "k": k,
        "mode": "shared" if sharing else "private",
        "tick_wall_s_median": walls[len(walls) // 2],
        "arrangement_bytes": arrangement_bytes(c),
        "imports": tm.stats["imports"],
        "exports": tm.stats["exports"],
    }


def main(argv=None) -> int:
    _maybe_cpu()
    ap = argparse.ArgumentParser(prog="bench_shared_mvs")
    ap.add_argument("--rows", type=int, default=3000)
    ap.add_argument("--ticks", type=int, default=8)
    ap.add_argument("--ks", default="1,2,4,8")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    suffix = _platform_suffix()
    ks = [int(x) for x in args.ks.split(",")]
    # discarded warmup scenarios: the first run in a process pays every XLA
    # compile, and spine-merge shapes evolve with the tick count — so warm
    # BOTH modes at the full tick count (pow2 buckets keep later scenarios
    # shape-identical) before anything is measured
    run_scenario(2, True, rows=args.rows, ticks=args.ticks)
    run_scenario(1, False, rows=args.rows, ticks=args.ticks)
    print("warmup done", flush=True)
    results = []
    for sharing in (True, False):
        for k in ks:
            r = run_scenario(k, sharing, rows=args.rows, ticks=args.ticks)
            results.append(r)
            print(
                f"k={r['k']} mode={r['mode']:7s} "
                f"tick={r['tick_wall_s_median'] * 1e3:8.1f} ms "
                f"arr={r['arrangement_bytes'] / 1e6:7.2f} MB "
                f"imports={r['imports']}",
                flush=True,
            )

    def med(mode, k, field):
        return next(
            r[field] for r in results if r["mode"] == mode and r["k"] == k
        )

    kmax = max(ks)
    doc = {
        "benchmark": f"shared_mvs{suffix}",
        "platform_suffix": suffix,
        "rows": args.rows,
        "ticks": args.ticks,
        "results": results,
        "scaling": {
            f"shared_k{kmax}_over_k1_tick": med("shared", kmax, "tick_wall_s_median")
            / med("shared", 1, "tick_wall_s_median"),
            f"private_k{kmax}_over_k1_tick": med("private", kmax, "tick_wall_s_median")
            / med("private", 1, "tick_wall_s_median"),
            f"shared_k{kmax}_over_k1_arr_bytes": med("shared", kmax, "arrangement_bytes")
            / med("shared", 1, "arrangement_bytes"),
            f"private_k{kmax}_over_k1_arr_bytes": med("private", kmax, "arrangement_bytes")
            / med("private", 1, "arrangement_bytes"),
        },
    }
    print(json.dumps(doc["scaling"], indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
