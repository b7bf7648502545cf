"""Records the small trace that `test_trace_reduce.py` checks the reduction on.

    python -m chipbench.tests.record_trace <out.xplane.pb>      (on the chip)

Three rounds. In each, inside a `chipbench.advance` annotation, `jit_small`
runs twice and `jit_big` once on the device; then the host sleeps 20 ms
inside a `chipbench.deliver` annotation with the device idle; then 10 ms
outside any annotation. So the reduction must find 6 executions of
`jit_small` and 3 of `jit_big`, 9 in all, `jit_big` ahead of `jit_small` by
device time, busy < window, and idle time charged to `deliver` of about 60 ms
and to `outside any request` of about 30 ms.
"""

import glob
import os
import shutil
import sys
import tempfile
import time


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    from chipbench.run import annotate, mark

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")

    @jax.jit
    def small(x):
        return x + 1

    @jax.jit
    def big(x):
        return jnp.sort(x * 3)

    x = jnp.arange(1 << 22, dtype=jnp.int32)
    small(x).block_until_ready()
    big(x).block_until_ready()
    d = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    options.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=options)
    mark("window_start")
    for _ in range(3):
        with annotate("advance"):
            small(x).block_until_ready()
            small(x).block_until_ready()
            big(x).block_until_ready()
        with annotate("deliver"):
            time.sleep(0.02)
        time.sleep(0.01)
    mark("window_end")
    jax.profiler.stop_trace()
    (found,) = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(found, out)
    shutil.rmtree(d)
    print("recorded", out, os.path.getsize(out), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
