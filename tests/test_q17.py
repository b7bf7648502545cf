"""TPC-H Q17 as the benchmark's second deployment serves it (ISSUE 30): the
published text through `Coordinator` over `LOAD GENERATOR TPCH`, with the
benchmark's seeded generator at the one seam, compared with the benchmark's
plain NumPy reference after hydration and after every refresh, by `SELECT`
and by a SUBSCRIBE's consolidated diffs. And the planner's NUMERIC rules the
query leans on: `0.2 * avg(x)` is a fifth of the average, a NUMERIC quotient
keeps six digits."""

import functools
from decimal import Decimal

import pytest

from chipbench.reference import tpch_q17 as ref
from chipbench.traffic.tpch_q17 import Generator
from materialize_tpu.adapter import Coordinator, coordinator
from materialize_tpu.obs.metrics import REGISTRY

Q17 = (
    "CREATE MATERIALIZED VIEW q17 AS SELECT sum(l_extendedprice) / 7.0 AS avg_yearly "
    "FROM lineitem, part WHERE p_partkey = l_partkey AND {part_filter} "
    "AND l_quantity < (SELECT 0.2 * avg(l_quantity) FROM lineitem WHERE l_partkey = p_partkey)"
)
CASES = {
    # seed 2: three parts of SF0.01's 2,000 pass the published pair (13 % of seeds select none)
    "published": ("p_brand = 7 AND p_container = 17", ref.part_filter, 2),
    "less_selective": ("p_brand < 13 AND p_container < 20", lambda b, c: (b < 13) & (c < 20), 5),
}


def _scale6(rows) -> dict:
    """The one served row in the reference's form. `execute` and an in-process
    subscription hand NUMERIC back as a Python float, so the exact scale-6
    integer is the nearest one (the float is within 3e-7 of it below 2e9)."""
    ((value,),) = list(rows)
    return {} if value is None else {"avg_yearly": int((Decimal(value) * 10**ref.SCALE).to_integral_value())}


def _reduce_samples(dataflow: str) -> dict:
    """The view's own samples of the three reduce families (the registry is the process's)."""
    out = {}
    for fam in REGISTRY.families():
        if fam.name.startswith("mzt_reduce_"):
            for labels, v in fam.samples:
                if dict(labels)["dataflow"] == dataflow:
                    out[(fam.name, labels)] = v[2] if fam.kind == "histogram" else v
    return out


@pytest.mark.parametrize("case", list(CASES))
def test_q17_equals_the_reference_after_every_refresh(monkeypatch, case):
    sql_filter, part_filter, seed = CASES[case]
    monkeypatch.setattr(coordinator, "TpchGenerator", functools.partial(Generator, seed=seed))
    c = Coordinator()
    c.execute("CREATE SOURCE tpch FROM LOAD GENERATOR TPCH (SCALE FACTOR 0.01)")
    c.execute(Q17.format(part_filter=sql_filter))
    gen = c.generators[0][0]
    gid = c.catalog.get("q17").global_id
    sub = c.execute("SUBSCRIBE q17 WITH (PROGRESS)").subscription
    subscribed: dict = {}

    def check() -> dict:
        for _ts, progress, diff, row in sub.drain():
            if not progress:
                subscribed[row] = subscribed.get(row, 0) + diff
        live_rows = {r: n for r, n in subscribed.items() if n}
        assert set(live_rows.values()) == {1}
        want = ref.q17(gen.live(), part_filter=part_filter)
        assert want, "the case's seed selects no part"
        assert _scale6(c.execute("SELECT * FROM q17").rows) == want
        assert _scale6(live_rows) == want
        return want

    answers = [check()]
    for _ in range(4):
        before = _reduce_samples(gid)
        c.advance()
        after = _reduce_samples(gid)
        answers.append(check())
        # /metrics: the per-part reduce stepped once (counted per call), the
        # keyless sum once where a row of the refresh passed the part filter (in
        # every refresh of the less selective case), groups changed, and the
        # gauge holds the per-part table's groups
        steps = [after[k] - before.get(k, 0) for k in after if k[0] == "mzt_reduce_step_duration_ns"]
        assert [n for n in steps if n] in ([1, 1], [1][: case == "published"])
        changed = sum(after[k] - before.get(k, 0) for k in after if k[0] == "mzt_reduce_groups_changed_total")
        assert changed > 0
        assert gen.n_part in {v for k, v in after.items() if k[0] == "mzt_reduce_state_groups"}
    if case == "less_selective":  # a quarter of the parts pass: the answer moves in every refresh
        assert all(a != b for a, b in zip(answers, answers[1:]))


@pytest.mark.parametrize(
    "select, want",
    [
        ("0.2 * avg(qty)", 3.05),  # 30.5 until PR 30: the literal's scale was lost against avg's float
        ("0.2 * avg(price)", 92.5),
        ("avg(qty) * 0.2", 3.05),
        ("sum(price) / 7.0", 264.285714),  # six digits, truncated (sql/plan.py's NUMERIC note)
        ("sum(qty) / 7.0", 8.714285),
    ],
)
def test_numeric_times_avg_and_numeric_division(select, want):
    c = Coordinator()
    c.execute("CREATE TABLE l (pk int, qty int, price numeric(12,2))")
    c.execute("INSERT INTO l VALUES (1,1,100.00),(1,50,250.50),(1,4,1000.00),(1,6,499.50)")
    assert c.execute(f"SELECT {select} FROM l").rows == [(want,)]


def test_numeric_times_avg_inside_a_correlated_predicate():
    c = Coordinator()
    c.execute("CREATE TABLE l (pk int, qty int)")
    # group 1: avg 15.25, a fifth is 3.05: 1 qualifies, 4 does not; group 2: avg 20, a fifth is 4: 4 does not
    c.execute("INSERT INTO l VALUES (1,1),(1,50),(1,4),(1,6),(2,4),(2,36)")
    rows = c.execute(
        "SELECT pk, qty FROM l WHERE qty < (SELECT 0.2 * avg(l2.qty) FROM l l2 WHERE l2.pk = l.pk)"
    ).rows
    assert rows == [(1, 1)]


def test_reduce_stepped_in_slices_equals_one_step(monkeypatch):
    """A delta wider than BULK_ROWS (a hydration snapshot) is stepped
    slice by slice: a later slice retracts what an earlier one emitted for the
    same group, so the view holds one row a group, the right one."""
    from materialize_tpu.dataflow import runtime

    c = Coordinator()
    c.execute("CREATE TABLE t (k int, v int)")
    rows = [(i % 7, i) for i in range(100)]
    c.execute("INSERT INTO t VALUES " + ",".join(f"({k},{v})" for k, v in rows))
    monkeypatch.setattr(runtime, "BULK_ROWS", 32)  # the snapshot's batch is 128 rows wide
    c.execute("CREATE MATERIALIZED VIEW g AS SELECT k, sum(v) AS s, count(*) AS n FROM t GROUP BY k")
    c.execute("CREATE MATERIALIZED VIEW a AS SELECT sum(v) AS s FROM t")
    want = {k: (sum(v for kk, v in rows if kk == k), sum(1 for kk, _ in rows if kk == k)) for k in range(7)}
    assert {k: (s, n) for k, s, n in c.execute("SELECT * FROM g").rows} == want
    assert c.execute("SELECT * FROM a").rows == [(sum(v for _, v in rows),)]
    c.execute("INSERT INTO t VALUES (3, 1000), (9, 1)")  # an ordinary tick: one step
    c.execute("DELETE FROM t WHERE k = 0")
    got = {k: (s, n) for k, s, n in c.execute("SELECT * FROM g").rows}
    want.pop(0)
    want[3] = (want[3][0] + 1000, want[3][1] + 1)
    want[9] = (1, 1)
    assert got == want


def test_matches_that_fit_the_floor_come_back_as_one_batch_of_that_size():
    """`join_against` with a floor (the join operators pass a sixteenth of the
    probe's capacity): matches that fit it in all are one batch of that
    capacity however they split over the arrangement's batches; more than
    that, or no floor, and every batch's output has its own bucket."""
    import numpy as np

    from materialize_tpu.arrangement.spine import arrange_batch
    from materialize_tpu.ops.join import join_against
    from materialize_tpu.repr.batch import UpdateBatch

    def keyed(keys):
        n = len(keys)
        cols = (np.asarray(keys, dtype=np.int64), np.arange(n, dtype=np.int64))
        return arrange_batch(UpdateBatch.build((), cols, np.zeros(n), np.ones(n, dtype=np.int64)), (0,))

    def rows(outs):
        return sorted(r for o in outs for r in o.to_rows())

    probe = keyed(list(range(1000)))
    a, b, none = keyed([1, 2, 3]), keyed(list(range(500, 540))), keyed([5000])
    for batches in ([a, b], [a, none], [b]):
        got = join_against(probe, batches, floor=64)
        assert [o.cap for o in got] == [64]
        assert rows(got) == rows(join_against(probe, batches))
    assert [o.cap for o in join_against(probe, [a, b])] == [8, 64]
    assert [o.cap for o in join_against(probe, [a, keyed(list(range(100)))], floor=64)] == [8, 128]
    assert join_against(probe, [none], floor=64) == []


@pytest.mark.parametrize(
    "select",
    ["avg(big)", "sum(big) / 7.0", "0.2 * avg(big)"],
)
def test_a_numeric_quotient_past_i64_is_an_error_not_a_wrapped_value(select):
    """The dividend is scaled up by 10^6 before the division (`mul_exact`): a
    sum of 1e13 would wrap there, and reads as `numeric overflow` instead."""
    c = Coordinator()
    c.execute("CREATE TABLE t (k int, big bigint)")
    c.execute("INSERT INTO t VALUES (1, 5000000000000), (1, 5000000000000), (2, 7)")
    c.execute(f"CREATE MATERIALIZED VIEW v AS SELECT k, {select} AS q FROM t GROUP BY k")
    with pytest.raises(Exception, match="numeric overflow"):
        c.execute("SELECT * FROM v")
    c.execute("DELETE FROM t WHERE k = 1")  # the error leaves with the rows that made it
    assert len(c.execute("SELECT * FROM v").rows) == 1


def test_bulk_outputs_sized_by_rows_keep_q3_exact(monkeypatch):
    """With the bulk bound pulled down to 512 rows, Q3's hydration and its
    refreshes go through both bulk rules (sliced reduce steps, operator
    outputs sized by their rows): the view equals the benchmark's plain Q3
    reference after hydration and after every refresh."""
    import json
    from pathlib import Path

    from chipbench.reference import tpch as q3_ref
    from chipbench.traffic.tpch import Generator as Q3Generator
    from materialize_tpu.dataflow import runtime

    config = json.loads((Path(q3_ref.__file__).parents[1] / "configs" / "loadgen_tpch_sf1_q3.json").read_text())
    monkeypatch.setattr(coordinator, "TpchGenerator", functools.partial(Q3Generator, seed=11))
    monkeypatch.setattr(runtime, "BULK_ROWS", 512)
    c = Coordinator()
    for sql in config["setup_sql"]:
        c.execute(sql.format(scale_factor="0.01"))
    gen = c.generators[0][0]
    parse = q3_ref.VIEWS["q3"][1]
    for refresh in range(4):
        if refresh:
            c.advance()
        want = q3_ref.q3(gen.live())
        assert want and parse(c.execute("SELECT * FROM q3").rows) == want, f"refresh {refresh}"
