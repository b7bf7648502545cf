"""SUBSCRIBE fan-out benchmark (PR 20): encode-once frame sharing.

Installs K subscribers on one materialized view and measures per-tick wall
time (the coordinator command that publishes the tick), full-drain wall
time, delivered bytes, and the encode counter, for K in {1, 100, 1000,
10000}. The fan-out contract says tick cost is O(1) in K — the dataflow
renders one consolidated frame per (collection, tick, format) into the
shared cursor ring and every subscriber holds a cursor, not a queue copy —
so the 10k-subscriber tick wall must sit within 3x of the 100-subscriber
tick wall, while delivered frames grow ~K x encodes.

Honest labeling (the bench.py rules): metrics are suffixed `_cpu_fallback`
unless the backend is a real TPU — absolute numbers from the XLA:CPU
fallback say nothing about TPU wall time; the K-scaling RATIOS are the
contract.

Usage:
  MZT_BENCH_CPU=1 python -m benchmarks.bench_fanout \
      [--ticks 8] [--out benchmarks/fanout_cpu_r20.json]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import time


def _maybe_cpu():
    if os.environ.get("MZT_BENCH_CPU") == "1":
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")


def _platform_suffix() -> str:
    import jax

    return "" if jax.devices()[0].platform == "tpu" else "_cpu_fallback"


def _run_k(k: int, ticks: int) -> dict:
    from materialize_tpu.adapter import Coordinator
    from materialize_tpu.egress.fanout import _DELIVERED, _ENCODED

    coord = Coordinator()
    coord.execute("CREATE TABLE t (a int)")
    coord.execute("CREATE MATERIALIZED VIEW mv AS SELECT a FROM t")
    subs = [
        coord.execute("SUBSCRIBE mv WITH (SNAPSHOT false, PROGRESS)")
        for _ in range(k)
    ]
    # flush the one-time per-subscriber preambles out of the measurement
    for out in subs:
        while out.subscription.pop_frame("pgcopy", timeout=0) is not None:
            pass
    e0 = _ENCODED.value(format="pgcopy")
    d0 = _DELIVERED.value(format="pgcopy")

    tick_walls, drain_walls, delivered_bytes = [], [], 0
    for j in range(ticks):
        t0 = time.perf_counter()
        coord.execute(f"INSERT INTO t VALUES ({j})")
        tick_walls.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        for out in subs:
            f = out.subscription.pop_frame("pgcopy", timeout=0)
            while f is not None:
                delivered_bytes += len(f.data)
                f = out.subscription.pop_frame("pgcopy", timeout=0)
        drain_walls.append(time.perf_counter() - t0)

    result = {
        "k": k,
        "ticks": ticks,
        "tick_wall_s_median": statistics.median(tick_walls),
        "drain_wall_s_median": statistics.median(drain_walls),
        "delivered_bytes": delivered_bytes,
        "frames_encoded": _ENCODED.value(format="pgcopy") - e0,
        "frames_delivered": _DELIVERED.value(format="pgcopy") - d0,
    }
    for out in subs:
        coord.teardown_subscription(out.status)
    return result


def main() -> None:
    _maybe_cpu()
    p = argparse.ArgumentParser()
    p.add_argument("--ticks", type=int, default=8)
    p.add_argument("--out", default=None)
    args = p.parse_args()

    suffix = _platform_suffix()
    results = []
    for k in (1, 100, 1000, 10000):
        r = _run_k(k, args.ticks)
        results.append(r)
        print(
            f"K={k:>6}: tick {r['tick_wall_s_median'] * 1e3:8.2f} ms  "
            f"drain {r['drain_wall_s_median'] * 1e3:8.2f} ms  "
            f"encoded {r['frames_encoded']:>6.0f}  "
            f"delivered {r['frames_delivered']:>8.0f}  "
            f"({r['delivered_bytes']} bytes)",
            flush=True,
        )

    by_k = {r["k"]: r for r in results}
    ratio = (
        by_k[10000]["tick_wall_s_median"] / by_k[100]["tick_wall_s_median"]
    )
    doc = {
        "benchmark": f"fanout{suffix}",
        "platform_suffix": suffix,
        "ticks": args.ticks,
        "results": results,
        "tick_wall_10k_over_100": ratio,
        "contract": "tick_wall(10k) <= 3 * tick_wall(100)",
        "contract_met": ratio <= 3.0,
    }
    print(f"tick wall 10k/100 ratio: {ratio:.2f} (contract: <= 3.0)")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {args.out}")
    if not doc["contract_met"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
