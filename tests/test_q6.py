"""TPC-H Q6 as the benchmark's third deployment serves it: the published text
(`INTERVAL '1' YEAR`, `BETWEEN 0.06 - 0.01 AND 0.06 + 0.01`) through
`Coordinator` over `LOAD GENERATOR TPCH` (TPC-H's eight tables, every
column), with the benchmark's seeded generator at the one seam, compared with the benchmark's plain NumPy reference
after hydration and after every refresh, by `SELECT` and by a SUBSCRIBE's
consolidated diffs. And the SQL-standard interval qualifier the text leans on:
`INTERVAL '<n>' <unit> [(<p>)]` plans exactly as `INTERVAL '<n> <unit>'`."""

import functools
import json
from decimal import Decimal
from pathlib import Path

import pytest

from chipbench.reference import tpch_q6 as ref
from chipbench.traffic.tpch_full import Generator
from materialize_tpu.adapter import Coordinator, coordinator
from materialize_tpu.adapter.coordinator import Lowerer, _collect_gets, optimize
from materialize_tpu.sql.parser import parse_statement
from materialize_tpu.sql.plan import PlanError

CONFIG = json.loads((Path(ref.__file__).parents[1] / "configs" / "loadgen_tpch_sf1_q6.json").read_text())


def _scale4(rows) -> dict:
    """The one served row in the reference's form. `execute` and an in-process
    subscription hand NUMERIC back as a Python float, so the exact scale-4
    integer is the nearest one (the float is within 1e-6 of it below 1e9)."""
    ((value,),) = list(rows)
    return {} if value is None else {"revenue": int((Decimal(value) * 10**ref.SCALE).to_integral_value())}


@pytest.mark.parametrize("seed", [11, 3000000401])
def test_q6_equals_the_reference_after_every_refresh(monkeypatch, seed):
    monkeypatch.setattr(coordinator, "TpchGenerator", functools.partial(Generator, seed=seed))
    c = Coordinator()
    for sql in CONFIG["setup_sql"]:  # the published text, as the benchmark sends it
        c.execute(sql.format(scale_factor="0.01"))
    gen = c.generators[0][0]
    sub = c.execute("SUBSCRIBE q6 WITH (PROGRESS)").subscription
    subscribed: dict = {}

    def check() -> dict:
        for _ts, progress, diff, row in sub.drain():
            if not progress:
                subscribed[row] = subscribed.get(row, 0) + diff
        live_rows = {r: n for r, n in subscribed.items() if n}
        assert set(live_rows.values()) == {1}
        want = ref.q6(gen.live())
        assert want, "no lineitem qualifies"
        assert _scale4(c.execute("SELECT * FROM q6").rows) == want
        assert _scale4(live_rows) == want
        return want

    answers = [check()]
    for _ in range(6):
        c.advance()
        answers.append(check())
    assert len(set(a["revenue"] for a in answers)) > 1  # some refresh moved the answer


def _lir(c: Coordinator, select: str):
    """What the view's dataflow would render: the optimized plan, lowered."""
    rel = optimize(c.planner.plan_query(parse_statement(select).query).mir, c.configs)
    env = {g: c.storage[g].dtypes for g in _collect_gets(rel)}
    return Lowerer(env, c._mono_ids()).lower(rel)


@pytest.fixture(scope="module")
def dates():
    c = Coordinator()
    c.execute("CREATE TABLE d (dt date)")
    c.execute("INSERT INTO d VALUES (DATE '1994-01-01'), (DATE '1994-03-31'), (DATE '1994-04-14'), "
              "(DATE '1994-12-31'), (DATE '1995-01-01')")
    return c


@pytest.mark.parametrize(
    "qualified, string",
    [
        ("'1' YEAR", "'1 year'"),
        ("'3' MONTH", "'3 months'"),
        ("'90' DAY (3)", "'90 days'"),
        ("'2' WEEK", "'2 weeks'"),
        ("'2' years", "'2 years'"),
    ],
)
def test_interval_qualifier_plans_as_the_string_form(dates, qualified, string):
    select = "SELECT dt FROM d WHERE dt < DATE '1994-01-01' + INTERVAL {}"
    got = _lir(dates, select.format(qualified))
    assert got == _lir(dates, select.format(string))
    assert got != _lir(dates, select.format("'1 day'"))
    assert dates.execute(select.format(qualified)).rows == dates.execute(select.format(string)).rows


@pytest.mark.parametrize("unit", ["HOUR", "minute (2)"])
def test_interval_qualifier_finer_than_a_day_is_refused(dates, unit):
    with pytest.raises(PlanError, match="unsupported"):
        dates.execute(f"SELECT dt FROM d WHERE dt < DATE '1994-01-01' + INTERVAL '1' {unit}")
