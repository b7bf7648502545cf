"""Feature benchmark: per-scenario wallclock, compared across builds.

The analogue of the reference's feature-benchmark methodology
(doc/developer/feature-benchmark.md:66-80 and
misc/python/materialize/feature_benchmark/): each scenario measures one
engine capability; runs are RECORDED to JSON and later runs COMPARE against a
recorded baseline, emitting a THIS vs OTHER verdict per scenario (regression
= ratio above threshold). Absolute numbers are environment-bound; the
verdicts are the contract.

Usage:
  python -m benchmarks.feature_bench --record baseline.json
  python -m benchmarks.feature_bench --compare baseline.json [--threshold 1.25]
  MZT_BENCH_CPU=1 … # force CPU
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


class Scenario:
    name = "base"
    iterations = 20

    def setup(self, coord):
        pass

    def before(self, coord, i):
        pass

    def measure(self, coord, i):
        raise NotImplementedError

    def run(self, coord) -> float:
        """Median per-iteration seconds (first iteration discarded: compile)."""
        self.setup(coord)
        times = []
        for i in range(self.iterations + 1):
            self.before(coord, i)
            t0 = time.perf_counter()
            self.measure(coord, i)
            times.append(time.perf_counter() - t0)
        times = sorted(times[1:])
        return times[len(times) // 2]


class Insert(Scenario):
    name = "insert"

    def setup(self, coord):
        coord.execute("CREATE TABLE ins_t (a int, b int)")

    def measure(self, coord, i):
        coord.execute(f"INSERT INTO ins_t VALUES ({i}, {i * 10})")


class FastPathPeek(Scenario):
    name = "fast_path_peek"

    def setup(self, coord):
        coord.execute("CREATE TABLE fp_t (a int, b int)")
        coord.execute(
            "INSERT INTO fp_t VALUES " + ", ".join(f"({i}, {i})" for i in range(200))
        )
        coord.execute(
            "CREATE MATERIALIZED VIEW fp_mv AS SELECT a, sum(b) AS s FROM fp_t GROUP BY a"
        )

    def measure(self, coord, i):
        coord.execute("SELECT * FROM fp_mv")


class MVUpdate(Scenario):
    name = "mv_update"
    iterations = 45  # capacity shapes stabilize ~25 ticks in; median = steady state

    def setup(self, coord):
        coord.execute("CREATE TABLE up_t (g int, v int)")
        coord.execute(
            "CREATE MATERIALIZED VIEW up_mv AS SELECT g, sum(v) AS s, count(*) AS n FROM up_t GROUP BY g"
        )

    def measure(self, coord, i):
        coord.execute(f"INSERT INTO up_t VALUES ({i % 7}, {i})")
        coord.execute("SELECT * FROM up_mv")


class DeltaJoinTick(Scenario):
    name = "delta_join_tick"
    iterations = 30

    def setup(self, coord):
        coord.execute("CREATE TABLE dj_a (k int, v int)")
        coord.execute("CREATE TABLE dj_b (k int, w int)")
        coord.execute("CREATE TABLE dj_c (w int, x int)")
        coord.execute(
            """CREATE MATERIALIZED VIEW dj AS
               SELECT dj_a.v, dj_c.x FROM dj_a, dj_b, dj_c
               WHERE dj_a.k = dj_b.k AND dj_b.w = dj_c.w"""
        )

    def measure(self, coord, i):
        coord.execute(f"INSERT INTO dj_a VALUES ({i}, {i})")
        coord.execute(f"INSERT INTO dj_b VALUES ({i}, {i + 1})")
        coord.execute(f"INSERT INTO dj_c VALUES ({i + 1}, {i + 2})")


class TopKTick(Scenario):
    name = "topk_tick"
    iterations = 35

    def setup(self, coord):
        coord.execute("CREATE TABLE tk_t (g int, v int)")
        coord.execute(
            "CREATE MATERIALIZED VIEW tk AS SELECT g, v FROM tk_t ORDER BY v DESC LIMIT 5"
        )

    def measure(self, coord, i):
        coord.execute(f"INSERT INTO tk_t VALUES ({i % 3}, {i * 7 % 101})")


class RecursiveTick(Scenario):
    name = "recursive_tick"
    iterations = 18

    def setup(self, coord):
        coord.execute("CREATE TABLE rc_e (s int, d int)")
        coord.execute(
            """CREATE MATERIALIZED VIEW rc AS
               WITH MUTUALLY RECURSIVE r (s int, d int) AS (
                 SELECT s, d FROM rc_e
                 UNION SELECT r.s, e.d FROM r, rc_e e WHERE r.d = e.s)
               SELECT s, d FROM r"""
        )

    def measure(self, coord, i):
        coord.execute(f"INSERT INTO rc_e VALUES ({i}, {i + 1})")


SCENARIOS = [Insert, FastPathPeek, MVUpdate, DeltaJoinTick, TopKTick, RecursiveTick]


def run_all() -> dict:
    from materialize_tpu.adapter import Coordinator

    out = {}
    for cls in SCENARIOS:
        coord = Coordinator()
        s = cls()
        out[s.name] = s.run(coord)
        print(f"# {s.name}: {out[s.name]*1000:.1f} ms", file=sys.stderr)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--record", metavar="FILE")
    ap.add_argument("--compare", metavar="FILE")
    ap.add_argument("--threshold", type=float, default=1.25)
    args = ap.parse_args()
    _maybe_cpu()
    results = run_all()
    if args.record:
        with open(args.record, "w") as f:
            json.dump(results, f, indent=2)
        print(f"recorded {len(results)} scenarios to {args.record}")
        return
    if args.compare:
        with open(args.compare) as f:
            other = json.load(f)
        worst = 0.0
        for name, this in results.items():
            base = other.get(name)
            if base is None:
                continue
            ratio = this / base
            worst = max(worst, ratio)
            verdict = "REGRESSION" if ratio > args.threshold else "ok"
            print(f"{name:20s} THIS {this*1000:8.1f}ms  OTHER {base*1000:8.1f}ms  x{ratio:.2f}  {verdict}")
        sys.exit(1 if worst > args.threshold else 0)
    print(json.dumps(results, indent=2))


if __name__ == "__main__":
    main()
