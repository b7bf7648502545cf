"""The Q6 plain reference at a few thousand rows on the CPU: against a
brute-force Python loop, the parser, and the control."""

import datetime

import numpy as np
import pytest

from chipbench.reference import tpch_q6 as ref
from chipbench.traffic.tpch_full import Generator


def _gen(seed, sf=0.001, refreshes=3):
    g = Generator(sf=sf, seed=seed)
    g.snapshot()
    for _ in range(refreshes):
        g.refresh_rows()
    return g


def _brute_force(live: dict) -> dict:
    """Q6 row by row, in the specification's terms: the ship date as a
    calendar date, the discount as a Decimal, the revenue in exact Decimals."""
    from decimal import Decimal

    epoch, lo = datetime.date(1992, 1, 1), datetime.date(1994, 1, 1)
    hi = lo.replace(year=lo.year + 1)
    total, hit = Decimal(0), False
    li = live["lineitem"]
    price, disc, ship, qty = (li[c].tolist() for c in ("l_extendedprice", "l_discount", "l_shipdate", "l_quantity"))
    for p, d, s, q in zip(price, disc, ship, qty):
        day, discount = epoch + datetime.timedelta(days=s), Decimal(d) / 100
        if lo <= day < hi and Decimal("0.05") <= discount <= Decimal("0.07") and q < 24:
            total, hit = total + Decimal(p) / 100 * discount, True
    return {"revenue": int(total.scaleb(ref.SCALE))} if hit else {}


def test_calendar():
    assert (ref.SHIP_FROM, ref.SHIP_TO) == (731, 1096)  # 1992 is a leap year


@pytest.mark.parametrize("seed", [1, 2, 3, 3000003401])
def test_q6_agrees_with_a_brute_force_loop(seed):
    live = _gen(seed).live()
    want = _brute_force(live)
    assert want and ref.q6(live) == want


def test_no_qualifying_row_is_an_empty_answer():
    live = _gen(5).live()
    live["lineitem"]["l_quantity"] = np.full_like(live["lineitem"]["l_quantity"], 24)
    assert ref.q6(live) == {} == _brute_force(live)


def test_parser_and_differ():
    _reference, parse = ref.VIEWS["q6"]
    assert parse([("32311447.3540",)]) == {"revenue": 323114473540}
    assert parse([(3062637.1711,)]) == {"revenue": 30626371711}  # JSON hands a number
    assert parse([(None,)]) == {} and parse([("\\N",)]) == {}  # no lineitem qualifies: one NULL row
    with pytest.raises(ValueError):
        parse([("1.23456",)])
    with pytest.raises(ValueError):
        parse([("1",), ("2",)])
    assert ref.differ({"revenue": 2}, {"revenue": 2}) == 0
    assert ref.differ({"revenue": 2}, {"revenue": 3}) == 1 and ref.differ({}, {"revenue": 3}) == 1


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_float32_comes_out_not_correct(seed):
    """The reference in float32 in the program's place goes through the
    comparison a run makes and comes out as not correct; at the cell's own
    size it was read at SF1 (PERF.md), here at the rehearsal's SF0.01."""
    from chipbench import run as bench_run
    from chipbench.control import control

    bench = bench_run.load_json(bench_run.ROOT / "BENCHMARK.json")
    config = bench_run.load_json(bench_run.ROOT / bench_run.one(bench["configs"], "loadgen_tpch_sf1_q6")["file"])
    out = control(config, seed, refreshes=14, scale=config["rehearse_scale_factor"])
    assert out["correct"] is False and out["reference_rows"] == 1
    failing = {k for k, c in out["checks"].items() if not bench_run.holds(c)}
    assert failing == {"subscribe_rows_differ", "pgwire_rows_differ", "http_rows_differ"}
