"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip TPU hardware is not available in CI; all sharding tests run against
``--xla_force_host_platform_device_count=8`` on CPU, mirroring how the
reference tests multi-process replicas without a cloud (SURVEY.md §4
"Multi-node without a real cluster"). Must run before jax is imported.
"""

import atexit
import faulthandler
import os
import shutil
import signal
import sys
import tempfile
import tracemalloc

# One compile per program per run: the process that starts the run (xdist's
# controller, or a plain `pytest`) makes a fresh persistent compile cache and
# exports it under JAX's own variables. xdist's workers and the replica
# subprocesses that tests spawn inherit it, so a program compiled by one
# process, or dropped by the per-module `jax.clear_caches()` below, comes
# back from disk. Fresh per run: every run does the same work and no entry
# outlives the code that made it.
if "PYTEST_XDIST_WORKER" not in os.environ:
    _cache_dir = tempfile.mkdtemp(prefix="mzt-test-jax-cache-")
    atexit.register(shutil.rmtree, _cache_dir, ignore_errors=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"

# Force, don't setdefault: unit tests must run on the virtual CPU mesh
# whatever platform the ambient env names.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_backend_optimization_level" not in flags:
    # thousands of tiny programs compile per suite run: O0 compiles are
    # faster and results are unchanged
    flags = (flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = flags

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


_programs = [0]


def _count_program(event, _duration, **_kw):
    if event == "/jax/core/compile/backend_compile_duration":
        _programs[0] += 1


jax.monitoring.register_event_duration_secs_listener(_count_program)


@pytest.fixture
def programs_built():
    """A callable giving how many programs this process has asked XLA for so
    far: every program jit did not hold in memory, whether the compiler built
    it or the persistent cache had it. Tests assert on differences of it."""
    return lambda: _programs[0]


@pytest.fixture
def device_tick_guard():
    """Wrap a dataflow's jitted tick in jax.transfer_guard("disallow").

    The CI assertion for the device exchange plane (doc/DEVICE_MESH.md): once
    installed, ANY host transfer issued while the jitted tick runs — an
    np.asarray pull, an implicit numpy-operand upload, an io_callback — fails
    the test loudly instead of silently serializing the mesh through the
    host. Install AFTER the first step: compilation itself transfers jit
    constants host→device once, which is legitimate and unrepeated.

    Guards both host directions only; device↔device movement (shard_map
    resharding inputs onto the mesh) is the exchange plane's job and stays
    allowed.
    """

    def install(df):
        inner = df._tick

        def guarded_tick(*args, **kwargs):
            with jax.transfer_guard_host_to_device("disallow"), \
                    jax.transfer_guard_device_to_host("disallow"):
                return inner(*args, **kwargs)

        df._tick = guarded_tick
        return df

    return install


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Free compiled XLA executables after each test module.

    A full suite run compiles thousands of small programs in one process;
    dropping them between modules bounds the native code a process holds
    (doc/ROADMAP.md "Known flake"). What the next module needs again comes
    back from the run's persistent cache, not from the compiler.
    """
    yield
    import jax

    jax.clear_caches()


# Every test has a limit of its own, so a hang fails by name and the run goes
# on instead of being found by the clock of the whole run.
TEST_LIMIT_S = 300


@pytest.fixture(autouse=True)
def _per_test_limit(request):
    def on_alarm(signum, frame):
        raise TimeoutError(f"{request.node.nodeid} ran past {TEST_LIMIT_S} s")

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(TEST_LIMIT_S)
    # a hang in native code never runs the handler above: dump every thread's
    # stack and end the process, which xdist reports as this test's crash
    faulthandler.dump_traceback_later(
        TEST_LIMIT_S + 60, exit=True, file=sys.__stderr__
    )
    yield
    faulthandler.cancel_dump_traceback_later()
    signal.alarm(0)
    signal.signal(signal.SIGALRM, previous)


@pytest.fixture(autouse=True)
def _tracemalloc_stays_off():
    """`/prof/heap` starts tracemalloc and a server never stops it; left on,
    every later test of the worker runs about ten times slower."""
    yield
    if tracemalloc.is_tracing():
        tracemalloc.stop()
        pytest.fail("the test left tracemalloc on")
