"""Per-kernel microbenchmark: registered XLA vs Pallas backends (PR 15).

Times each registered hot-path kernel (run_sum, multi_take, probe, probe2)
through BOTH backends over a capacity sweep, with untraced
``time.perf_counter`` around warmed jitted callables (block_until_ready
inside the timed region — host wall time is the metric that matters on the
dispatch-bound tick path).

Honest labeling (the bench.py rules): metrics are suffixed ``_cpu_fallback``
unless the backend is a real TPU, and on CPU the Pallas side additionally
carries ``interpret`` in its label — interpret mode is an op-by-op XLA
EMULATION of the kernel program, so its absolute time says nothing about a
Mosaic-compiled kernel on a chip. On CPU this artifact records (a) the XLA
reference cost per kernel per shape and (b) proof that the Pallas path runs
end-to-end; the XLA-vs-Pallas RATIO is only meaningful on TPU.

Usage:
  MZT_BENCH_CPU=1 python -m benchmarks.bench_kernels \
      [--sizes 1024,4096,16384] [--iters 30] [--out benchmarks/kernels_cpu_r15.json]
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


def _cases(n: int):
    """Representative inputs per kernel at capacity n (tick-shaped dtypes)."""
    import numpy as np

    import jax.numpy as jnp

    rng = np.random.default_rng(15)
    flags = rng.random(n) < 0.3
    flags[0] = True
    sum_cols = tuple(
        jnp.asarray(rng.integers(-(2**40), 2**40, n).astype(np.int64))
        for _ in range(3)
    )
    take_cols = (
        jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32)),
        jnp.asarray(rng.integers(-(2**50), 2**50, n).astype(np.int64)),
        jnp.asarray(rng.integers(-(2**50), 2**50, n).astype(np.int64)),
        jnp.asarray(rng.integers(0, 2**31, n).astype(np.uint32)),
        jnp.asarray(rng.integers(-(2**20), 2**20, n).astype(np.int64)),
    )
    idx = jnp.asarray(rng.permutation(n).astype(np.int32))
    sorted_a = jnp.asarray(
        np.sort(rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32))
    )
    queries = jnp.asarray(
        rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32)
    )
    hi = jnp.asarray(np.sort(rng.integers(0, 64, n).astype(np.uint32)))
    lo = sorted_a
    return {
        "run_sum": (jnp.asarray(flags), sum_cols),
        "multi_take": (take_cols, idx),
        "probe": (sorted_a, queries),
        "probe2": (hi, lo, queries, queries),
    }


def _timed(fn, args, iters: int):
    """Median wall seconds per call over `iters` untraced perf_counter laps."""
    import jax

    out = fn(*args)  # warmup: pays the trace + compile
    jax.block_until_ready(out)
    laps = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        laps.append(time.perf_counter() - t0)
    laps.sort()
    return laps[len(laps) // 2]


def main(argv=None) -> int:
    _maybe_cpu()
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="1024,4096,16384")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from materialize_tpu.ops import kernels

    suffix = _platform_suffix()
    interp = kernels.pallas_interpret()
    results = []
    for n in (int(x) for x in args.sizes.split(",")):
        cases = _cases(n)
        for name, ins in cases.items():
            for backend in ("xla", "pallas"):

                def call(*a, _name=name, _backend=backend):
                    with kernels.using_backend(_backend):
                        return kernels.dispatch(_name, *a)

                fn = jax.jit(call)
                sec = _timed(fn, ins, args.iters)
                label = backend + ("_interpret" if backend == "pallas" and interp else "")
                results.append(
                    {
                        "kernel": name,
                        "backend": label,
                        "n": n,
                        "wall_s_median": sec,
                    }
                )
                print(
                    f"n={n:6d} {name:10s} {label:16s} {sec * 1e6:10.1f} us",
                    flush=True,
                )

    devs = jax.devices()
    doc = {
        "benchmark": f"kernels{suffix}",
        "platform_suffix": suffix,
        "pallas_interpret": interp,
        "iters": args.iters,
        # device topology: a forced-8-device CPU run must be distinguishable
        # from a 1-device run in the artifact (kernel timings are per-device
        # programs, so mesh_axis is 1 — but n_devices records the ambient)
        "n_devices": len(devs),
        "mesh_axis": {"workers": 1},
        "note": (
            "pallas_interpret=true means the Pallas timings are op-by-op XLA "
            "emulation (correctness proof, not kernel performance); compare "
            "xla-vs-pallas only when platform_suffix is empty (real TPU)"
        ),
        "results": results,
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
