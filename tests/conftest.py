"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip TPU hardware is not available in CI; all sharding tests run against
``--xla_force_host_platform_device_count=8`` on CPU, mirroring how the
reference tests multi-process replicas without a cloud (SURVEY.md §4
"Multi-node without a real cluster"). Must run before jax is imported.
"""

import os

# Force, don't setdefault: unit tests must run on the virtual CPU mesh
# whatever platform the ambient env names.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_backend_optimization_level" not in flags:
    # thousands of tiny programs compile per suite run; at the default opt
    # level the XLA:CPU compiler intermittently segfaulted late in long
    # processes (see doc/ROADMAP.md "Known flake") — O0 compiles are faster
    # and exercise a lighter codegen path, results are unchanged
    flags = (flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = flags

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Pallas registers its tpu-platform lowering rules at import time; import it
# here so every test module sees one consistent registration order.
from jax.experimental import pallas as _pallas  # noqa: E402,F401

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


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
    past a cumulative threshold the XLA:CPU compiler segfaulted (always in
    the last, compile-heaviest module — see doc/ROADMAP.md "Known flake").
    Dropping executables between modules keeps native code volume bounded;
    modules recompile what they need.
    """
    yield
    import jax

    jax.clear_caches()
