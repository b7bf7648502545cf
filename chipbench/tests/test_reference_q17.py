"""The Q17 generator subclass and its plain reference, at a few hundred rows
on the CPU: against a brute-force Python loop, the parser, and the control."""

import numpy as np
import pytest

from chipbench.reference import tpch_q17 as ref
from chipbench.traffic.tpch import Generator as Base
from chipbench.traffic.tpch_q17 import Generator


def _gen(seed, sf=0.001, refreshes=3):
    g = Generator(sf=sf, seed=seed)
    g.snapshot()
    for _ in range(refreshes):
        g.refresh_rows()
    return g


def _brute_force(live: dict, keep) -> dict:
    """Q17 row by row: per part the list of its quantities, then each lineitem
    against a fifth of its part's average, in exact rational arithmetic."""
    from fractions import Fraction

    parts = {int(k) for k, b, c in zip(*live["part"]) if keep(int(b), int(c))}
    qty_of: dict = {}
    for pk, q in zip(live["lineitem"][5].tolist(), live["lineitem"][4].tolist()):
        qty_of.setdefault(pk, []).append(q)
    cents, hit = 0, False
    for pk, q, price in zip(live["lineitem"][5].tolist(), live["lineitem"][4].tolist(), live["lineitem"][1].tolist()):
        if pk in parts and q < Fraction(1, 5) * Fraction(sum(qty_of[pk]), len(qty_of[pk])):
            cents, hit = cents + price, True
    return {"avg_yearly": cents * 10**5 // 70} if hit else {}


def test_same_streams_as_the_parent_generator_and_part_kept():
    a, b = _gen(7), Base(sf=0.001, seed=7)
    b.snapshot()
    for _ in range(3):
        b.refresh_rows()
    for t in ("customer", "orders", "lineitem"):
        assert all(np.array_equal(x, y) for x, y in zip(a.live()[t], b.live()[t]))
    pk, brand, container = a.live()["part"]
    assert len(pk) == 200 and brand.max() < 25 and container.max() < 40
    assert all(np.array_equal(x, y) for x, y in zip(a.live()["part"], _gen(7).live()["part"]))


@pytest.mark.parametrize("seed", [1, 2, 3, 4254])
def test_q17_agrees_with_a_brute_force_loop(seed):
    # 200 parts, about 6,000 lineitems. The published pair selects 1 part in 1,000,
    # so a wider pair of the same form carries the comparison; the published one
    # is compared too (both sides usually empty at this size)
    live = _gen(seed).live()
    wide = lambda b, c: (b < 13) & (c < 20)  # noqa: E731
    want = _brute_force(live, wide)
    assert want and ref.q17(live, part_filter=wide) == want
    assert ref.q17(live) == _brute_force(live, lambda b, c: b == ref.BRAND and c == ref.CONTAINER)


def test_parser_and_differ():
    _reference, parse = ref.VIEWS["q17"]
    assert parse([("5011288.842857",)]) == {"avg_yearly": 5011288842857}
    assert parse([(264.285714,)]) == {"avg_yearly": 264285714}  # JSON hands a number
    assert parse([(None,)]) == {} and parse([("\\N",)]) == {}  # no lineitem qualifies: one NULL row
    with pytest.raises(ValueError):
        parse([("1.2345678",)])
    with pytest.raises(ValueError):
        parse([("1",), ("2",)])
    assert ref.differ({"avg_yearly": 2}, {"avg_yearly": 2}) == 0
    assert ref.differ({"avg_yearly": 2}, {"avg_yearly": 3}) == 1 and ref.differ({}, {"avg_yearly": 3}) == 1


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_float32_comes_out_not_correct(seed):
    """The reference in float32 in the program's place goes through the
    comparison a run makes and comes out as not correct; at the cell's own
    size it was read at SF1 (PERF.md), here at the rehearsal's SF0.02."""
    from chipbench import run as bench_run
    from chipbench.control import control

    bench = bench_run.load_json(bench_run.ROOT / "BENCHMARK.json")
    config = bench_run.load_json(bench_run.ROOT / bench_run.one(bench["configs"], "loadgen_tpch_sf1_q17")["file"])
    out = control(config, seed, refreshes=12, scale=config["rehearse_scale_factor"])
    assert out["correct"] is False and out["reference_rows"] == 1
    failing = {k for k, c in out["checks"].items() if not bench_run.holds(c)}
    assert failing == {"subscribe_rows_differ", "pgwire_rows_differ", "http_rows_differ"}
