"""A reduce step's outputs are sized by what the step knows: it hands on no
error batch its own count says is empty (`step_counts`' third number, read
with the two the host already read), and a keyless reduce, which holds one
group, emits at KEYLESS_OUT_CAP rows whatever its input's capacity. Counts,
never a wall clock: consolidate calls and their capacity outside jit, the
`mzt_reduce_error_batches_total` counter, output capacities, programs
requested (`jax.monitoring`, conftest's `programs_built`)."""

import functools
import importlib
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

import chipbench
from chipbench.reference import tpch_q6 as ref
from chipbench.traffic.tpch_full import Generator
from materialize_tpu.adapter import Coordinator, coordinator
from materialize_tpu.arrangement.trace_manager import SharedReduceTrace
from materialize_tpu.dataflow import runtime
from materialize_tpu.errors import sqlstate_of
from materialize_tpu.expr import CallBinary, Column
from materialize_tpu.expr.scalar import EvalErr
from materialize_tpu.obs.metrics import REGISTRY
from materialize_tpu.ops.reduce import KEYLESS_OUT_CAP, AggregateExpr
from materialize_tpu.repr import UpdateBatch
from materialize_tpu.repr.batch import bucket_cap

from test_q6 import CONFIG as Q6, _scale4

CONFIGS = Path(chipbench.__file__).parent / "configs"
WORKLOADS = Path(chipbench.__file__).parent / "workloads"


def _err_batches(dataflow: str) -> dict:
    """{outcome: count} of the dataflow's `mzt_reduce_error_batches_total`."""
    out = {"empty": 0, "carried": 0}
    for fam in REGISTRY.families():
        if fam.name == "mzt_reduce_error_batches_total":
            for labels, v in fam.samples:
                if dict(labels)["dataflow"] == dataflow:
                    out[dict(labels)["outcome"]] += v
    return out


def _reduces(c: Coordinator, view: str) -> tuple[str, list]:
    gid = c.catalog.get(view).global_id
    df = next(d for g, d, _ in c.dataflows if g == gid)
    return gid, [n for _, ops, _ in df.builds for n, _ in ops if isinstance(n, runtime._REDUCE_NODES)]


def _recorded(monkeypatch, node) -> list:
    """Every delta `node.step` returns, as it returns it."""
    seen, real = [], node.step

    def step(tick, ins):
        d = real(tick, ins)
        seen.append(d)
        return d

    monkeypatch.setattr(node, "step", step)
    return seen


def _consolidate_calls(monkeypatch) -> list:
    """(capacity, live rows) of every `consolidate` called outside jit, under
    whichever module's name it was imported."""
    real = importlib.import_module("materialize_tpu.ops.consolidate").consolidate
    calls = []

    def counted(batch, compact=True):
        if not isinstance(batch.hashes, jax.core.Tracer):
            calls.append((batch.cap, int(batch.count())))
        return real(batch, compact)

    for name, mod in list(sys.modules.items()):
        if name.startswith("materialize_tpu") and getattr(mod, "consolidate", None) is real:
            monkeypatch.setattr(mod, "consolidate", counted)
    return calls


def test_q6_refreshes_carry_no_error_batch_and_two_rows_of_capacity(monkeypatch):
    """Q6's published text at SF0.01 through `Coordinator.advance()`: the
    keyless fused step hands on no error batch, says so once per refresh, and
    leaves at KEYLESS_OUT_CAP rows; the refresh consolidates outside jit some
    320-450 rows of capacity (9,504-18,976 before, 85 % of it empty error
    batches at three to seven times the delta), and the view equals the
    reference by SELECT and by SUBSCRIBE."""
    monkeypatch.setattr(coordinator, "TpchGenerator", functools.partial(Generator, seed=11))
    c = Coordinator()
    for sql in Q6["setup_sql"]:
        c.execute(sql.format(scale_factor="0.01"))
    gen = c.generators[0][0]
    gid, (node,) = _reduces(c, "q6")
    assert isinstance(node, runtime.FusedMfpReduceNode) and node.key_cols == ()
    sub = c.execute("SUBSCRIBE q6 WITH (PROGRESS)").subscription
    subscribed: dict = {}
    stepped = _recorded(monkeypatch, node)
    calls = _consolidate_calls(monkeypatch)
    answers = set()
    for _ in range(6):
        before, calls[:] = _err_batches(gid), []
        c.advance()
        assert _err_batches(gid) == {"empty": before["empty"] + 1, "carried": before["carried"]}
        ((out, errs),) = stepped[-1:]
        assert errs is None and out.cap == KEYLESS_OUT_CAP == bucket_cap(2)
        assert sum(cap for cap, _ in calls) < 1024, calls
        for _ts, progress, diff, row in sub.drain():
            if not progress:
                subscribed[row] = subscribed.get(row, 0) + diff
        want = ref.q6(gen.live())
        assert want and _scale4(c.execute("SELECT * FROM q6").rows) == want
        assert _scale4({r: n for r, n in subscribed.items() if n}) == want
        answers.add(want["revenue"])
    assert len(stepped) == 6 and len(answers) > 1


BAD_ROW = "(1, 1, 0, 1e12, 'x')"  # a division by zero for a / b, a fixed-point overflow for sum(f)


@pytest.mark.parametrize(
    "view, node_type, keyed, error",
    [
        ("SELECT k, sum(a / b) AS s FROM t GROUP BY k", runtime.FusedMfpReduceNode, True, "division by zero"),
        ("SELECT sum(a / b) AS s FROM t WHERE k > 0", runtime.FusedMfpReduceNode, False, "division by zero"),
        ("SELECT k, sum(f) AS s FROM t GROUP BY k", runtime.FusedMfpReduceNode, True, "numeric overflow"),
        # a string function keeps the MFP its own node, so the reduce is not fused
        ("SELECT k, sum(a / b) AS s FROM t WHERE upper(s) = 'X' GROUP BY k", runtime.ReduceNode, True,
         "division by zero"),
        ("SELECT sum(a / b) AS s FROM t WHERE upper(s) = 'X'", runtime.ReduceNode, False, "division by zero"),
        # over the table itself: the reduce steps a shared trace
        ("SELECT sum(a / b) AS s FROM t", runtime.SharedReduceNode, False, "division by zero"),
        ("SELECT sum(f) AS s FROM t", runtime.SharedReduceNode, False, "numeric overflow"),
    ],
    ids=["fused_keyed", "fused_keyless", "fused_keyed_overflow", "private_keyed", "private_keyless",
         "shared_keyless", "shared_keyless_overflow"],
)
def test_an_aggregate_error_reaches_the_view_and_its_retraction_clears_it(view, node_type, keyed, error):
    """The error path is unchanged: a refresh whose aggregate errors carries
    the error batch (counted `carried`) to the view's error collection, a
    SELECT fails with XX000 and the error's text, as before; the refresh that
    retracts the bad row carries the retraction, and a division by zero's
    error is gone with it; a refresh with no error carries none."""
    c = Coordinator()
    c.execute("CREATE TABLE t (k int, a int, b int, f float, s text)")
    c.execute("INSERT INTO t VALUES (1, 10, 2, 1.5, 'x'), (2, 6, 3, 2.5, 'x')")
    c.execute(f"CREATE MATERIALIZED VIEW v AS {view}")
    gid, (node,) = _reduces(c, "v")
    assert type(node) is node_type
    clean = sorted(c.execute("SELECT * FROM v").rows)
    assert len(clean) == (2 if keyed else 1)

    def wrote(sql: str) -> dict:
        before = _err_batches(gid)
        c.execute(sql)
        after = _err_batches(gid)
        return {k: after[k] - before[k] for k in after}

    assert wrote(f"INSERT INTO t VALUES {BAD_ROW}") == {"empty": 0, "carried": 1}
    with pytest.raises(Exception) as caught:
        c.execute("SELECT * FROM v")
    assert sqlstate_of(caught.value) == "XX000" and error in str(caught.value)
    assert wrote("DELETE FROM t WHERE b = 0") == {"empty": 0, "carried": 1}
    if error == "numeric overflow":
        # as before this change: a fixed-point overflow's error row is +1 at
        # every step that flags it (the retraction's contribution is flagged
        # too), so the view keeps the error
        with pytest.raises(Exception, match=error):
            c.execute("SELECT * FROM v")
    else:
        assert sorted(c.execute("SELECT * FROM v").rows) == clean
    assert wrote("INSERT INTO t VALUES (3, 9, 3, 0.5, 'x')") == {"empty": 1, "carried": 0}
    if error != "numeric overflow":
        assert len(c.execute("SELECT * FROM v").rows) == (3 if keyed else 1)


@pytest.mark.parametrize("key_cols", [(0,), ()], ids=["keyed", "keyless"])
def test_a_shared_reduce_trace_carries_only_the_errors_it_holds(key_cols):
    """SharedReduceTrace stepped directly (the SQL surface renders a keyed
    reduce over a table as a fused step): None where its step's error batch
    is empty, the batch where it holds the error and where it retracts it;
    the trace's error arrangement ends empty."""
    aggs = (AggregateExpr("sum", CallBinary("div", Column(1), Column(2))),)
    i64 = np.dtype(np.int64)
    trace = SharedReduceTrace("g", key_cols, aggs, (i64, i64, i64), exporter="x")

    def step(tick, rows, diffs):
        cols = tuple(np.asarray(c, dtype=np.int64) for c in zip(*rows))
        return trace.step(tick, UpdateBatch.build((), cols, [tick] * len(rows), diffs), runtime._reduce_in_slices)

    out, errs = step(1, [(1, 10, 2), (2, 6, 3)], [1, 1])
    assert errs is None and out.cap == (2 * 8 if key_cols else KEYLESS_OUT_CAP)
    out, errs = step(2, [(1, 1, 0)], [1])
    assert [(r[0], r[2]) for r in errs.to_rows()] == [((int(EvalErr.DIVISION_BY_ZERO),), 1)]
    assert out is not None and int(out.count()) == 0
    out, errs = step(3, [(1, 1, 0)], [-1])
    assert [r[2] for r in errs.to_rows()] == [-1]
    out, errs = step(4, [(2, 9, 3)], [1])
    assert errs is None
    assert sorted(r[0] for r in out.to_rows()) == ([(2, 2), (2, 5)] if key_cols else [(7,), (10,)])
    assert trace.snapshot(4)[1] is None  # the error and its retraction cancel


@pytest.mark.parametrize("query", ["q3", "q17", "q6"])
def test_no_reduce_output_capacity_changes_between_refreshes(monkeypatch, programs_built, query):
    """Each cell's text at SF0.015 (where lineitem's and orders' refresh
    deltas sit inside their buckets: SF0.01's 120-odd lineitems cross 128),
    seed 11: from the cell's own warm-ups on and through refresh 14 (refresh
    15 is tick 16, the view's self-correction) every reduce leaves each step
    at the one capacity, a keyless one at KEYLESS_OUT_CAP, and no refresh
    asks XLA for a program."""
    config = json.loads((CONFIGS / f"loadgen_tpch_sf1_{query}.json").read_text())
    workload = next(w for w in json.loads((Path(chipbench.__file__).parents[1] / "BENCHMARK.json").read_text())[
        "workloads"] if w["config"] == config["name"])
    warmups = json.loads((WORKLOADS / f"{workload['traffic']}.json").read_text())["warmups"]
    module, name = config["generator"]["class"].split(":")
    monkeypatch.setattr(
        coordinator, "TpchGenerator", functools.partial(getattr(importlib.import_module(module), name), seed=11)
    )
    c = Coordinator()
    for sql in config["setup_sql"]:
        c.execute(sql.format(scale_factor="0.015"))
    _gid, nodes = _reduces(c, config["view"])
    stepped = [_recorded(monkeypatch, n) for n in nodes]
    for _ in range(warmups):
        c.advance()
    for seen in stepped:
        seen.clear()
    built = programs_built()
    for _ in range(warmups, 14):
        c.advance()
    assert programs_built() == built
    for node, seen in zip(nodes, stepped):
        caps = {d[0].cap for d in seen if d is not None and d[0] is not None}
        assert len(caps) == 1, (type(node).__name__, node.key_cols, caps)
        if node.key_cols == ():
            assert caps == {KEYLESS_OUT_CAP}
    assert any(n.key_cols == () for n in nodes) == (query != "q3")
