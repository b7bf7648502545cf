"""The reduction from trace events to metrics: on hand-made events whose
answer is known, and on the small trace recorded on the chip in PR 26
(`data/small.xplane.pb`, made by `record_trace.py`)."""

import os

import pytest

from chipbench import trace_reduce as tr
from chipbench import work

MS = 1_000_000  # ns


def _events():
    host_main = [
        (0, 10, "chipbench.window_start"),
        (1 * MS, 41 * MS, "chipbench.advance"),
        (2 * MS, 30 * MS, "PjitFunction(_consolidate)"),
        (41 * MS, 61 * MS, "chipbench.deliver"),
        (100 * MS, 100 * MS + 10, "chipbench.window_end"),
    ]
    modules = [
        (5 * MS, 15 * MS, "jit__consolidate(111)"),
        (20 * MS, 25 * MS, "jit__consolidate(222)"),
        (30 * MS, 40 * MS, "jit__join_materialize(7)"),
        (200 * MS, 210 * MS, "jit__consolidate(111)"),  # after the window: not counted
    ]
    ops = [(5 * MS, 9 * MS), (9 * MS, 15 * MS), (20 * MS, 25 * MS), (30 * MS, 35 * MS), (34 * MS, 40 * MS),
           (200 * MS, 210 * MS)]
    return {"devices": [{"name": "/device:TPU:0", "modules": modules, "ops": ops}], "host": [sorted(host_main)]}


def test_reduce_hand_made_events():
    out = tr.reduce_events(_events())
    assert out["window_s"] == pytest.approx(0.1, rel=1e-3)
    assert out["busy_s"] == pytest.approx(0.025)  # 10 + 5 + 10 ms, overlapping ops merged
    assert out["executions"] == 3
    assert out["programs"]["jit__consolidate"] == {"count": 2, "seconds": pytest.approx(0.015)}
    assert out["device_ops"][0][0] == "jit__consolidate"
    gaps = dict(out["idle_gaps"])
    # 1-5, 15-20 and 25-30 ms lie inside advance and inside its pjit call
    assert gaps["advance>PjitFunction(_consolidate)"] == pytest.approx(0.014, rel=1e-2)
    assert gaps["advance"] == pytest.approx(0.001, rel=1e-2)  # 40-41 ms
    assert gaps["deliver"] == pytest.approx(0.020, rel=1e-2)  # 41-61 ms of the gap 40-100
    assert gaps["outside any request"] == pytest.approx(0.040, rel=1e-2)  # 0-1 and 61-100 ms
    assert sum(gaps.values()) + out["busy_s"] == pytest.approx(out["window_s"], rel=1e-3)


def test_reduce_refuses_a_trace_without_marks_or_device():
    ev = _events()
    ev["host"] = [[e for e in ev["host"][0] if "window" not in e[2]]]
    with pytest.raises(ValueError):
        tr.reduce_events(ev)
    with pytest.raises(ValueError):
        tr.reduce_events({"devices": [], "host": _events()["host"]})


def test_program_name():
    assert tr.program_name("jit__consolidate(1234)") == "jit__consolidate"
    assert tr.program_name("jit_f") == "jit_f"


def test_recorded_chip_trace():
    """`record_trace.py` ran `jit_small` 6 times and `jit_big` 3 times between
    the marks. The device's clock in the trace runs about 1 ms ahead of the
    host's (PERF.md, PR 26), so the first short programs fall before the
    window's first mark: 4 to 6 of `jit_small` are inside."""
    path = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")
    out = tr.reduce_trace(path)
    assert out["programs"]["jit_big"]["count"] == 3 and 4 <= out["programs"]["jit_small"]["count"] <= 6
    assert out["executions"] == 3 + out["programs"]["jit_small"]["count"]
    assert out["device_ops"][0][0] == "jit_big"
    assert 0.015 < out["busy_s"] < 0.03 < out["window_s"] < 0.2
    gaps = dict(out["idle_gaps"])
    deliver = sum(v for k, v in gaps.items() if k.startswith("deliver"))
    outside = sum(v for k, v in gaps.items() if k.startswith("outside"))
    assert 0.055 < deliver < 0.08 and 0.025 < outside < 0.05
    assert sum(gaps.values()) + out["busy_s"] == pytest.approx(out["window_s"], rel=1e-6)


def test_bytes_from_shapes_and_roofline_reader():
    import materialize_tpu  # noqa: F401  (x64, as in a run)
    import jax.numpy as jnp

    from chipbench.readers import device_idle, roofline

    a = (jnp.zeros((1024,), jnp.uint32), (jnp.zeros((1024,), jnp.int64),))
    assert work.tree_bytes(a) == 1024 * 4 + 1024 * 8
    assert work.call_bytes((a,), {"cap": 3}, a[0]) == 1024 * 12 + 1024 * 4
    run = {"trace": {"programs": {"jit__k": {"count": 1, "seconds": 1e-3}}, "busy_s": 0.25, "window_s": 1.0},
           "work": {"bytes": {"k": 819_000}}, "peaks": {"hbm_bytes_per_s": 819e9}}
    assert roofline.read(run, programs={"k": "jit__k"}) == pytest.approx(0.1)
    assert roofline.read(run, programs={"other": "jit__other"}) is None  # nothing to read: no 0
    assert device_idle.read(run) == pytest.approx(75.0)


def test_recorder_counts_calls_outside_jit_only():
    import jax
    import jax.numpy as jnp

    import importlib

    from materialize_tpu.repr.batch import UpdateBatch

    mod = importlib.import_module("materialize_tpu.ops.consolidate")  # `ops.consolidate` is the function

    rec = work.Recorder()
    rec.wrap_all({"consolidate": "materialize_tpu.ops.consolidate:consolidate"}, "materialize_tpu")
    try:
        b = UpdateBatch.build((), (jnp.arange(8),), [1] * 8, [1] * 8)
        mod.consolidate(b)
        jax.jit(lambda x: mod.consolidate(x))(b)  # inlined into another program: not a call of its own
        assert rec.calls == {"consolidate": 1}
        assert rec.bytes["consolidate"] == 2 * work.tree_bytes(b)
    finally:
        rec.unwrap()
    assert mod.consolidate.__module__ == "materialize_tpu.ops.consolidate" and not rec._undo
