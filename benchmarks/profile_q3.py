"""Capture a jax profiler trace of the fused Q3 steady-state tick on device.

Reuses bench.py's builders (same shapes → warm persistent compile cache).
Writes the trace under /tmp/mzt_profile/ and prints the top ops by self time
if the trace JSON is parseable.

Usage: python benchmarks/profile_q3.py  (env knobs as bench.py)
"""

import glob
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

LOGDIR = os.environ.get("MZT_PROFILE_DIR", "/tmp/mzt_profile")


def main():
    import jax

    from bench import _phase, build_tpu_side

    sf = float(os.environ.get("MZT_BENCH_SF", "0.1"))
    ticks = int(os.environ.get("MZT_BENCH_TICKS", "5"))
    frac = float(os.environ.get("MZT_BENCH_FRAC", "0.005"))

    gen, init, caps, step, state = build_tpu_side(sf, ticks, frac, 0, 1)
    from materialize_tpu.models.fused_q3 import hydrate
    from materialize_tpu.repr import UpdateBatch

    _phase("hydrating")
    state = hydrate(state, init["customer"], init["orders"], init["lineitem"], 1)
    jax.block_until_ready(state.accum.levels[-1].nrows)
    empty_c = UpdateBatch.empty(8, (), (np.dtype(np.int64),) * 3)
    refreshes = []
    for t in range(2, 2 + ticks + 1):
        r = gen.refresh(t, frac=frac)
        refreshes.append((t, r))

    _phase("warmup (compile-cache expected warm)")
    t0, r0 = refreshes[0]
    state, out, errs, over = step(state, empty_c, r0["orders"], r0["lineitem"], np.uint64(t0))
    jax.block_until_ready(out.diffs)
    _phase("warmup done; tracing ticks")

    jax.profiler.start_trace(LOGDIR)
    start = time.perf_counter()
    for t, r in refreshes[1:]:
        state, out, errs, over = step(state, empty_c, r["orders"], r["lineitem"], np.uint64(t))
    jax.block_until_ready(out.diffs)
    elapsed = time.perf_counter() - start
    jax.profiler.stop_trace()
    _phase(f"traced {ticks} ticks in {elapsed:.3f}s ({elapsed/ticks*1000:.0f} ms/tick)")

    kernel_report(int(state.accum.levels[-1].hashes.shape[-1]))
    report()


def kernel_report(cap: int, iters: int = 20):
    """Isolated per-kernel wall times at the run's arrangement capacity, for
    both registered backends — untraced perf_counter around warmed jitted
    calls, so the numbers attribute the tick's probe/gather/consolidate terms
    without trusting trace-event self-time accounting."""
    import jax
    import numpy as np

    from benchmarks.bench_kernels import _cases, _timed
    from materialize_tpu.ops import kernels

    interp = kernels.pallas_interpret()
    print(f"# registered kernels at cap={cap} (pallas_interpret={interp}):")
    cases = _cases(cap)
    for name, ins in cases.items():
        row = [f"{name:10s}"]
        for backend in ("xla", "pallas"):

            def call(*a, _n=name, _b=backend):
                with kernels.using_backend(_b):
                    return kernels.dispatch(_n, *a)

            sec = _timed(jax.jit(call), ins, iters)
            label = backend + ("~interp" if backend == "pallas" and interp else "")
            row.append(f"{label}={sec * 1e6:9.1f}us")
        print("  " + "  ".join(row))


def report():
    paths = sorted(glob.glob(f"{LOGDIR}/**/*.trace.json.gz", recursive=True))
    if not paths:
        print("no trace.json.gz found; files:", file=sys.stderr)
        for p in glob.glob(f"{LOGDIR}/**/*", recursive=True):
            print("  ", p, file=sys.stderr)
        return
    with gzip.open(paths[-1], "rt") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])
    # find device-lane complete events; aggregate duration by op name
    agg = {}
    total = 0.0
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name = ev.get("name", "")
        dur = ev.get("dur", 0) / 1e6  # us -> s
        cat = str(ev.get("args", {}))
        agg.setdefault(name, [0.0, 0])
        agg[name][0] += dur
        agg[name][1] += 1
        total += dur
    top = sorted(agg.items(), key=lambda kv: -kv[1][0])[:40]
    print(f"# trace {paths[-1]}: {len(events)} events, {total:.3f}s total span time")
    for name, (dur, cnt) in top:
        print(f"{dur:9.4f}s  x{cnt:<6d} {name[:120]}")


if __name__ == "__main__":
    if os.environ.get("MZT_REPORT_ONLY") == "1":
        report()
    else:
        main()
