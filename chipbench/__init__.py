"""chipbench — the benchmark of materialize_tpu (BENCHMARK.json at the root).

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

configs/    one file per deployment: SQL, scale, guarantees, generator, reference
workloads/  one file per traffic mix: loop kind, warm-ups, refreshes in the window
metrics/    one file per metric: layer, unit, what it moves, its reader and parameters
readers/    the small readers the metric files name
traffic/    the seeded load generators the configurations name
reference/  the plain references `correct` is decided against
run.py      the harness: names no cell, configuration or metric
clients.py  pgwire / SUBSCRIBE / HTTP clients; work.py bytes from shapes;
trace_reduce.py  .xplane.pb -> busy, programs, gaps; peaks.json the chip's peaks
control.py  the control of `correct`: the reference in float32 through a run's comparison
tests/      run by hand on the CPU (tier-1 runs tests/ only)
"""
