"""Ask the chip's compiler, without the chip.

The TPU compiler is installed in the test environment and compiles for a
*described* v5e that is not attached, so what it refuses costs no chip time.
A CPU run of the same function proves nothing about that. This file keeps
the six hot-path primitives, the consolidate sort and the head merge at
n = 2^22 with the column dtypes the served TPC-H Q3 path passes (u32 hashes
and device times, i32/i64 values, i64 diffs), the full-schema lineitem's
snapshot consolidate and head merge, TPC-H Q6's keyless fused reduce step
at its hydration slice and at a refresh's delta (its output cut to the two
rows a keyless reduce can hold), and TPC-H Q18's per-order reduce over its
2,097,152-row table (marked slow: outside tier-1) and the programs of its
TopK: all must compile.

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

from materialize_tpu.ops.consolidate import run_sum
from materialize_tpu.ops.permute import multi_take
from materialize_tpu.ops.search import merge_perm, searchsorted, searchsorted2
from materialize_tpu.parallel.devicemesh.exchange import bucket_rank, route_dest
from materialize_tpu.repr import UpdateBatch
from materialize_tpu.repr.batch import DIFF_DTYPE, TIME_DTYPE

N = 1 << 22  # SF1 lineitem arranges ~3.2 M rows

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


def _primitives(s):
    """{name: (function, positional shape specs, static kwargs)}, Q3-path dtypes."""
    return {
        # diffs are i64; Q3's revenue accumulators are i64, counts i32
        "run_sum": (run_sum, (_col(s, jnp.bool_), (_col(s, I64), _col(s, I32))), {}),
        # one permute of a lineitem-shaped payload: hash, vals, time, diff
        "multi_take": (
            multi_take,
            (
                (
                    _col(s, U32), _col(s, I32), _col(s, I32),
                    _col(s, I64), _col(s, TIME_DTYPE), _col(s, DIFF_DTYPE),
                ),
                _col(s, I32),
            ),
            {},
        ),
        "searchsorted": (searchsorted, (_col(s, U32), _col(s, U32)), {"side": "left"}),
        "searchsorted2": (
            searchsorted2, tuple(_col(s, U32) for _ in range(4)), {"side": "right"}
        ),
        # a head merge's order: the N-row head against its N / 16-row delta
        "merge_perm": (
            merge_perm,
            (_col(s, U32), _col(s, U32), _col(s, U32, N // 16), _col(s, U32, N // 16)),
            {},
        ),
        "route_dest": (route_dest, (_col(s, U32),), {"n_dest": 4}),
        "bucket_rank": (bucket_rank, (_col(s, I32),), {}),
    }


@pytest.mark.parametrize(
    "name",
    (
        "run_sum", "multi_take", "searchsorted", "searchsorted2", "merge_perm",
        "route_dest", "bucket_rank",
    ),
)
def test_xla_lowering_compiles_for_v5e(one_chip, name):
    fn, args, static = _primitives(one_chip)[name]
    compiled = jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile()
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
    compiled = consolidate_mod._consolidate.lower(batch, compact=True).compile()
    mem = compiled.memory_analysis()
    # fits one v5e's 16 GB with room for the arrangements around it
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 4 << 30


@pytest.mark.parametrize(
    "vals,n",
    [(16, 1 << 23)],
    ids=["lineitem_snapshot_8388608"],
)
def test_source_snapshot_consolidate_compiles_for_v5e(one_chip, vals, n):
    """A view's hydration reads its source's snapshot consolidated
    (`StorageCollection.snapshot`): at SF1 the full-schema lineitem is 16 i64
    columns at 8,388,608 rows of capacity, with no key."""
    batch = UpdateBatch(
        _col(one_chip, U32, n),
        (),
        tuple(_col(one_chip, I64, n) for _ in range(vals)),
        _col(one_chip, TIME_DTYPE, n),
        _col(one_chip, DIFF_DTYPE, n),
    )
    consolidate_mod = importlib.import_module("materialize_tpu.ops.consolidate")
    mem = consolidate_mod._consolidate.lower(batch, compact=True).compile().memory_analysis()
    # the input and the source's own spines (some 2.4 GB at SF1) stay beside it
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 8 << 30


@pytest.mark.parametrize(
    "vals,d",
    [(6, 1 << 14), (1, 1 << 15), (15, 1 << 14)],
    ids=["lineitem_262144_16384", "q17_averages_524288_32768", "lineitem_full_262144_16384"],
)
def test_head_merge_compiles_for_v5e(one_chip, vals, d):
    """The one program a refresh runs per arrangement (arrangement/spine.py):
    a delta merged into the fixed-capacity head, padded and truncated to the
    head's capacity inside the program. At the two widest shapes the
    benchmark's cells run: lineitem's 16,384-row delta (at Q3's and Q17's
    six columns and at the full schema's sixteen, one of them the key), and
    the 32,768-row delta of Q17's per-part averages."""
    from materialize_tpu.arrangement.spine import HEAD_RATIO

    def rows(n):  # hash, one i64 key, `vals` i64 columns, time, diff
        return UpdateBatch(
            _col(one_chip, U32, n),
            (_col(one_chip, I64, n),),
            tuple(_col(one_chip, I64, n) for _ in range(vals)),
            _col(one_chip, TIME_DTYPE, n),
            _col(one_chip, DIFF_DTYPE, n),
        )

    consolidate_mod = importlib.import_module("materialize_tpu.ops.consolidate")
    compiled = consolidate_mod._merge_consolidate.lower(
        rows(HEAD_RATIO * d),
        rows(d),
        jax.ShapeDtypeStruct((), TIME_DTYPE, sharding=one_chip),
        out_cap=HEAD_RATIO * d,
    ).compile()
    out = jax.tree_util.tree_leaves(compiled.out_info)
    assert {o.shape for o in out} == {(HEAD_RATIO * d,)}
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 1 << 30


@pytest.fixture(scope="module")
def q6_step():
    """The arguments of Q6's one render operator, the keyless fused reduce
    step, as the served path makes them: the benchmark configuration's
    published text over LOAD GENERATOR TPCH at a toy scale, on the CPU, with
    the step's call recorded. (table, delta, time, static keywords)."""
    import json
    from pathlib import Path

    from materialize_tpu.adapter import Coordinator
    from materialize_tpu.ops import fused_reduce

    config = Path(__file__).parents[1] / "chipbench" / "configs" / "loadgen_tpch_sf1_q6.json"
    calls = []
    real = fused_reduce.fused_mfp_reduce_step

    def recorded(*args):
        calls.append(args)
        return real(*args)

    fused_reduce.fused_mfp_reduce_step = recorded
    try:
        c = Coordinator()
        for sql in json.loads(config.read_text())["setup_sql"]:
            c.execute(sql.format(scale_factor="0.0001"))
    finally:
        fused_reduce.fused_mfp_reduce_step = real
    state, delta, time, mfp, key_cols, aggs = calls[-1]
    assert key_cols == () and delta.vals  # keyless, over lineitem's columns
    return state, delta, time, {"mfp": mfp, "key_cols": key_cols, "aggs": aggs}


@pytest.mark.parametrize(
    "state_cap,delta_cap",
    [(1 << 21, 1 << 21), (8, 1 << 14)],
    ids=["hydration_slice_2097152", "refresh_delta_16384"],
)
def test_q6_fused_reduce_step_compiles_for_v5e(one_chip, q6_step, state_cap, delta_cap):
    """Q6's step as SF1 asks for it: hydration steps lineitem's snapshot in
    BULK_ROWS slices against a table held at BULK_ROWS (dataflow/runtime.py),
    a refresh steps the 16,384-row lineitem delta against the one-group table
    at its bucket of 8. Either way the keyless step's output leaves it at
    KEYLESS_OUT_CAP rows, beside its four counts (groups, changed groups,
    error rows, the table merge's collision flag) for the host's one read."""
    from materialize_tpu.dataflow.runtime import BULK_ROWS
    from materialize_tpu.ops.fused_reduce import _fused_mfp_reduce_step
    from materialize_tpu.ops.reduce import KEYLESS_OUT_CAP

    assert BULK_ROWS == 1 << 21
    state, delta, time, static = q6_step

    def at(tree, n):
        return jax.tree_util.tree_map(lambda x: _col(one_chip, x.dtype, n), tree)

    compiled = _fused_mfp_reduce_step.lower(
        at(state, state_cap), at(delta, delta_cap),
        jax.ShapeDtypeStruct((), jnp.asarray(time).dtype, sharding=one_chip), **static,
    ).compile()
    _state, out, _errs, counts = compiled.out_info
    assert {o.shape for o in jax.tree_util.tree_leaves(out)} == {(KEYLESS_OUT_CAP,)}
    assert counts.shape == (4,)
    mem = compiled.memory_analysis()
    # one slice's temporaries (2.3 GB at the hydration slice) beside the source's spines
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 4 << 30


@pytest.fixture(scope="module")
def q18_calls():
    """The arguments of Q18's per-order reduce step, as the served path makes
    them, and the plan of its TopK: the benchmark configuration's published
    text over LOAD GENERATOR TPCH at a toy scale, on the CPU, stopped at the
    first reduce step of its hydration (the per-order one) once the dataflow,
    its TopK included, is built. The TopK's input is the view's six columns."""
    import json
    from pathlib import Path

    from materialize_tpu.adapter import Coordinator
    from materialize_tpu.dataflow import runtime
    from materialize_tpu.ops import fused_reduce

    config = Path(__file__).parents[1] / "chipbench" / "configs" / "loadgen_tpch_sf1_q18.json"
    source, view = json.loads(config.read_text())["setup_sql"]
    calls, plans = [], []

    class Recorded(Exception):
        pass

    def reduce_recorded(*args):
        calls.append(args)
        raise Recorded

    def topk_recorded(node, tplan):
        plans.append(tplan)
        real_topk(node, tplan)

    real_reduce, real_topk = fused_reduce.fused_mfp_reduce_step, runtime.TopKNode.__init__
    fused_reduce.fused_mfp_reduce_step, runtime.TopKNode.__init__ = reduce_recorded, topk_recorded
    try:
        c = Coordinator()
        c.execute(source.format(scale_factor="0.0001"))
        with pytest.raises(Exception):
            c.execute(view)
    finally:
        fused_reduce.fused_mfp_reduce_step, runtime.TopKNode.__init__ = real_reduce, real_topk
    ((state, delta, time, mfp, key_cols, aggs),) = calls
    assert len(key_cols) == 1 and len(delta.vals) == 16  # l_orderkey, over lineitem's columns
    (plan,) = plans
    return {
        "reduce": (state, delta, time, {"mfp": mfp, "key_cols": key_cols, "aggs": aggs}),
        "topk": (UpdateBatch.empty(8, (), (jnp.int64,) * 6), plan, time),
    }


def _shaped(tree, sharding, n):
    return jax.tree_util.tree_map(lambda x: _col(sharding, x.dtype, n), tree)


@pytest.mark.slow
@pytest.mark.parametrize(
    "delta_cap", [1 << 21, 1 << 14], ids=["hydration_slice_2097152", "refresh_delta_16384"]
)
def test_q18_per_order_reduce_compiles_for_v5e(one_chip, q18_calls, delta_cap):
    """Q18's subquery, a sum of quantities per order, holds one group per
    order: 1.5 M at SF1, a table of 2,097,152 rows of capacity, and
    hydration steps lineitem's snapshot against it in BULK_ROWS slices
    (dataflow/runtime.py); a refresh steps lineitem's 16,384-row delta
    against it. Both merge the delta into the table. Slow: the slice's
    compile takes minutes of five cores alone, and beside Q6's hydration
    compile in a tier-1 run each came near the 300 s limit."""
    from materialize_tpu.dataflow.runtime import BULK_ROWS
    from materialize_tpu.ops.fused_reduce import _fused_mfp_reduce_step

    assert BULK_ROWS == 1 << 21
    state, delta, time, static = q18_calls["reduce"]
    compiled = _fused_mfp_reduce_step.lower(
        _shaped(state, one_chip, 1 << 21), _shaped(delta, one_chip, delta_cap),
        jax.ShapeDtypeStruct((), jnp.asarray(time).dtype, sharding=one_chip), **static,
    ).compile()
    _state, _out, _errs, counts = compiled.out_info
    assert counts.shape == (4,)
    mem = compiled.memory_analysis()
    # the hydration slice's 2.36 GB of temporaries and 0.47 GB of output beside the source's spines
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 4 << 30


def test_q18_topk_programs_compile_for_v5e(one_chip, q18_calls):
    """The TopK over Q18's one group (ORDER BY o_totalprice DESC, o_orderdate
    LIMIT 100), at shapes past what the cell gives it: the affected groups'
    keys over a 4,096-row delta, the count pass and the gather over a
    65,536-row arrangement batch, the window over 1,024 gathered rows."""
    from materialize_tpu.ops import topk

    delta, plan, time = q18_calls["topk"]
    assert plan.group_cols == () and plan.limit == 100
    t = jax.ShapeDtypeStruct((), jnp.asarray(time).dtype, sharding=one_chip)
    probes = _shaped(delta, one_chip, 1 << 12)
    arr = _shaped(delta, one_chip, 1 << 16)
    topk._distinct_keys.lower(probes).compile()
    topk._gather_total_jit.lower(probes, arr).compile()
    topk._gather_materialize_jit.lower(probes, arr, out_cap=1 << 10).compile()
    compiled = topk._topk_select.lower(
        _shaped(delta, one_chip, 1 << 10), plan.order_by, plan.limit, plan.offset, t, plan.nulls_last
    ).compile()
    assert {o.shape for o in jax.tree_util.tree_leaves(compiled.out_info)} == {(1 << 10,)}
