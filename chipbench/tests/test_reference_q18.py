"""The Q18 plain reference at toy size on the CPU: against a hand-computed
case, against a brute-force Python loop over the generator's rows, the
parser, and the control; and the cell's generator against the one it derives
from."""

import numpy as np
import pytest

from chipbench.reference import tpch_q18 as ref
from chipbench.traffic.tpch_full import Generator


def _live(customers, orders, lines) -> dict:
    """A live-rows dict of the generator's form from (custkey,), (orderkey,
    custkey, cents, date) and (orderkey, quantity) tuples."""
    col = lambda rows, i: np.array([r[i] for r in rows], dtype=np.int64)  # noqa: E731
    return {
        "customer": {"c_custkey": col(customers, 0)},
        "orders": {"o_orderkey": col(orders, 0), "o_custkey": col(orders, 1),
                   "o_totalprice": col(orders, 2), "o_orderdate": col(orders, 3)},
        "lineitem": {"l_orderkey": col(lines, 0), "l_quantity": col(lines, 1)},
    }


def test_a_hand_computed_case():
    """Order 10 sums to 301 (kept), 11 to exactly 300 (not: HAVING is
    strict), 12 and 13 to 350 and 320 (kept), 14 has no lines. 12 and 13
    tie on price and date: the tie goes by c_custkey, so LIMIT 2 keeps 10
    (the dearest) and 13 (customer 1 before customer 2)."""
    live = _live(
        [(0,), (1,), (2,)],
        [(10, 0, 500_00, 100), (11, 0, 900_00, 50), (12, 2, 400_00, 70), (13, 1, 400_00, 70), (14, 1, 999_00, 1)],
        [(10, 50)] * 6 + [(10, 1), (11, 50), (11, 250), (12, 50)] + [(12, 60)] * 5 + [(13, 20), (13, 300)],
    )
    assert ref.q18(live) == {
        ("Customer#000000000", 0, 10, 100, 500_00, 301): 1,
        ("Customer#000000002", 2, 12, 70, 400_00, 350): 1,
        ("Customer#000000001", 1, 13, 70, 400_00, 320): 1,
    }
    assert ref.q18(live, limit=2) == {
        ("Customer#000000000", 0, 10, 100, 500_00, 301): 1,
        ("Customer#000000001", 1, 13, 70, 400_00, 320): 1,
    }
    assert ref.q18(live, quantity=400) == {}


def _gen(seed, sf=0.001, refreshes=3):
    g = Generator(sf=sf, seed=seed)
    g.snapshot()
    for _ in range(refreshes):
        g.refresh_rows()
    return g


def _brute_force(live: dict, quantity: int, limit: int) -> dict:
    """Q18 row by row: a dict of each order's lines, the orders past the
    threshold with their customer, Python's sort on the ORDER BY keys and
    the TopK's tie rule."""
    sums: dict = {}
    for k, q in zip(live["lineitem"]["l_orderkey"].tolist(), live["lineitem"]["l_quantity"].tolist()):
        sums[k] = sums.get(k, 0) + q
    o = live["orders"]
    rows = [
        (f"Customer#{c:09d}", c, k, d, p, sums[k])
        for k, c, p, d in zip(o["o_orderkey"].tolist(), o["o_custkey"].tolist(),
                              o["o_totalprice"].tolist(), o["o_orderdate"].tolist())
        if sums.get(k, 0) > quantity
    ]
    rows.sort(key=lambda r: (-r[4], r[3], r[1], r[2], r[5]))
    return {r: 1 for r in rows[:limit]}


@pytest.mark.parametrize("seed", [1, 2, 3000003601])
@pytest.mark.parametrize("quantity,limit", [(300, 100), (150, 100), (150, 7)])
def test_q18_agrees_with_a_brute_force_loop(seed, quantity, limit):
    live = _gen(seed).live()
    want = _brute_force(live, quantity, limit)
    assert ref.q18(live, quantity=quantity, limit=limit) == want
    if quantity == 150:
        assert len(want) == limit or limit == 100 and 0 < len(want) < 100


def test_parser_and_differ():
    _reference, parse = ref.VIEWS["q18"]
    row = ("Customer#000000037", 37, 10672, 1369, 31692478, 318)
    assert parse([("Customer#000000037", "37", "10672", "1369", "316924.78", "318")]) == {row: 1}
    assert parse([("Customer#000000037", 37, 10672, 1369, 316924.78, 318)]) == {row: 1}  # JSON hands numbers
    assert parse([]) == {}
    with pytest.raises(ValueError):
        parse([("Customer#000000037", "37", "10672", "1369", "316924.781", "318")])
    with pytest.raises(ValueError):
        parse([row[:4] + ("316924.78", 318)] * 2)
    other = row[:4] + (31692477, 318)
    assert ref.differ({row: 1}, {row: 1}) == 0
    assert ref.differ({other: 1}, {row: 1}) == 2 and ref.differ({}, {row: 1}) == 1


def test_control_float32_comes_out_not_correct():
    """The reference in float32 in the program's place goes through the
    comparison a run makes and comes out as not correct: o_totalprice in
    cents needs up to 27 bits, float32 holds 24. At the cell's own size it
    was read at SF1 (PERF.md); here at the rehearsal's SF0.01, on a seed whose
    one row's price float32 cannot hold (at SF0.01 the answer is one or two
    orders where it is not empty, and a price below 2^24 cents, or an even
    one below 2^25, survives float32)."""
    from chipbench import run as bench_run
    from chipbench.control import control

    bench = bench_run.load_json(bench_run.ROOT / "BENCHMARK.json")
    config = bench_run.load_json(bench_run.ROOT / bench_run.one(bench["configs"], "loadgen_tpch_sf1_q18")["file"])
    out = control(config, 3000003604, refreshes=14, scale=config["rehearse_scale_factor"])
    assert out["correct"] is False and out["reference_rows"] >= 1
    failing = {k for k, c in out["checks"].items() if not bench_run.holds(c)}
    assert failing == {"subscribe_rows_differ", "pgwire_rows_differ", "http_rows_differ"}
