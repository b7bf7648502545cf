"""Ask the chip's compiler, without the chip (doc/KERNELS.md "Interpret mode").

The TPU compiler is installed in the test environment and compiles for a
*described* v5e that is not attached. Interpret-mode byte-identity
(tests/test_kernels.py) can never see what it refuses, so this file keeps:

- the XLA lowering of every registered kernel, and the consolidate sort, at
  n = 2^22 with the column dtypes the served TPC-H Q3 path passes (u32
  hashes and device times, i32/i64 values, i64 diffs) — all must compile;
- one strict xfail per Pallas program with interpret forced off IN THE TEST:
  each is refused today, which is why `kernel_backend = auto` resolves to
  xla (registry.resolve_backend). The PR that repairs a kernel flips its case.

The topology is described inside a module-scoped fixture and nowhere else:
only one process may load the TPU library, and every xdist worker imports
every test module. Compiles happen in the test's own process with the
persistent compilation cache off around them (an entry compiled for a
described chip cannot be read back without one).
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from materialize_tpu.ops.kernels import registry
from materialize_tpu.repr import UpdateBatch
from materialize_tpu.repr.batch import DIFF_DTYPE, TIME_DTYPE

N = 1 << 22  # the XLA lowerings' width: SF1 lineitem arranges ~3.2 M rows
N_PALLAS = 1 << 16  # refused at every size tried (2^10, 2^16, 2^20)

U32, I32, I64 = jnp.uint32, jnp.int32, jnp.int64


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _col(sharding, dtype, n=N):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def _kernel_args(name: str, s, n: int):
    """(positional shape specs, static kwargs) per kernel, Q3-path dtypes."""
    if name == "run_sum":
        # diffs are i64; Q3's revenue accumulators are i64, counts i32
        return (_col(s, jnp.bool_, n), (_col(s, I64, n), _col(s, I32, n))), {}
    if name == "multi_take":
        # one permute of a lineitem-shaped payload: hash, vals, time, diff
        cols = (
            _col(s, U32, n), _col(s, I32, n), _col(s, I32, n),
            _col(s, I64, n), _col(s, TIME_DTYPE, n), _col(s, DIFF_DTYPE, n),
        )
        return (cols, _col(s, I32, n)), {}
    if name == "probe":
        return (_col(s, U32, n), _col(s, U32, n)), {"side": "left"}
    if name == "probe2":
        return tuple(_col(s, U32, n) for _ in range(4)), {"side": "right"}
    if name == "route_dest":
        return (_col(s, U32, n),), {"n_dest": 4}
    if name == "bucket_rank":
        return (_col(s, I32, n),), {}
    raise AssertionError(name)


def _compile(name: str, backend: str, sharding, n: int):
    impl = registry._KERNELS[name][backend]
    args, static = _kernel_args(name, sharding, n)
    return jax.jit(lambda *a: impl(*a, **static)).lower(*args).compile()


KERNELS = ("run_sum", "multi_take", "probe", "probe2", "route_dest", "bucket_rank")


def test_cases_cover_the_registry():
    assert sorted(KERNELS) == registry.registered_kernels()


@pytest.mark.parametrize("name", KERNELS)
def test_xla_lowering_compiles_for_v5e(one_chip, name):
    compiled = _compile(name, "xla", one_chip, N)
    assert "tpu_custom_call" not in compiled.as_text()


def test_consolidate_sort_compiles_for_v5e(one_chip):
    """The hottest program of the tick: 3-operand u32 sort + run merge +
    compaction over an orders-shaped batch (ops/consolidate.py)."""
    batch = UpdateBatch(
        _col(one_chip, U32),
        (_col(one_chip, I32),),
        (_col(one_chip, I32), _col(one_chip, I32), _col(one_chip, I64)),
        _col(one_chip, TIME_DTYPE),
        _col(one_chip, DIFF_DTYPE),
    )
    # (`materialize_tpu.ops.consolidate` the attribute is the function)
    consolidate_mod = importlib.import_module("materialize_tpu.ops.consolidate")
    compiled = consolidate_mod._consolidate.lower(
        batch, compact=True, backend="xla"
    ).compile()
    mem = compiled.memory_analysis()
    # fits one v5e's 16 GB with room for the arrangements around it
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 4 << 30


def test_head_merge_compiles_for_v5e(one_chip):
    """The one program a refresh runs per arrangement (arrangement/spine.py):
    a 16,384-row lineitem delta merged into the fixed-capacity head, padded
    and truncated to the head's capacity inside the program."""
    from materialize_tpu.arrangement.spine import HEAD_RATIO

    d = 1 << 14

    def lineitem(n):
        return UpdateBatch(
            _col(one_chip, U32, n),
            (_col(one_chip, I64, n),),
            tuple(_col(one_chip, I64, n) for _ in range(6)),
            _col(one_chip, TIME_DTYPE, n),
            _col(one_chip, DIFF_DTYPE, n),
        )

    consolidate_mod = importlib.import_module("materialize_tpu.ops.consolidate")
    compiled = consolidate_mod._merge_consolidate.lower(
        lineitem(HEAD_RATIO * d),
        lineitem(d),
        jax.ShapeDtypeStruct((), TIME_DTYPE, sharding=one_chip),
        backend="xla",
        out_cap=HEAD_RATIO * d,
    ).compile()
    out = jax.tree_util.tree_leaves(compiled.out_info)
    assert {o.shape for o in out} == {(HEAD_RATIO * d,)}
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 1 << 30


# The chip compiler's refusal per Pallas program (JAX 0.9.0, v5e:2x2). Every
# program also lacks a grid/BlockSpec, so a whole column would have to sit
# in fast memory even if it lowered.
PALLAS_REFUSALS = {
    "run_sum": (
        jax.errors.JaxRuntimeError,
        "UNIMPLEMENTED: While rewriting computation to not contain X64 element "
        "types ... tpu_custom_call (diffs are i64); on 32-bit columns alone, "
        "MosaicError: Invalid vector register cast (shift-by-d concatenate of "
        "unaligned lane slices over a (1, n) tile)",
    ),
    "bucket_rank": (
        Exception,  # MosaicError is private to jax._src
        "MosaicError: Mosaic failed to compile TPU kernel: Invalid vector "
        "register cast (shift-by-d concatenate of unaligned lane slices over "
        "a (1, n) tile)",
    ),
    "probe": (
        NotImplementedError,
        "Only 2D gather is supported (jnp.take on a reshaped 1-D ref)",
    ),
    "probe2": (
        NotImplementedError,
        "Only 2D gather is supported (jnp.take on a reshaped 1-D ref)",
    ),
    "multi_take": (ValueError, "Shape mismatch in input, indices and output"),
    "route_dest": (
        RecursionError,
        "maximum recursion depth exceeded while lowering h_ref[...] % nd",
    ),
}


@pytest.mark.parametrize(
    "name",
    [
        pytest.param(
            k,
            marks=pytest.mark.xfail(
                strict=True, raises=PALLAS_REFUSALS[k][0], reason=PALLAS_REFUSALS[k][1]
            ),
        )
        for k in KERNELS
    ],
)
def test_pallas_program_compiles_for_v5e(one_chip, monkeypatch, name):
    # interpret forced off here, in the test: off-chip the registry would
    # pick interpret mode, which is pure XLA and always compiles
    monkeypatch.setattr(registry, "pallas_interpret", lambda: False)
    compiled = _compile(name, "pallas", one_chip, N_PALLAS)
    assert "tpu_custom_call" in compiled.as_text()
