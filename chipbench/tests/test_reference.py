"""The seeded generator copy and the plain references, at SF0.001 on the CPU."""

import numpy as np
import pytest

from chipbench.reference import tpch as ref
from chipbench.traffic.tpch import SEGMENTS, Generator


def _gen(seed, sf=0.001, refreshes=3):
    g = Generator(sf=sf, seed=seed)
    g.snapshot()
    for _ in range(refreshes):
        g.refresh_rows()
    return g


def test_same_seed_same_rows_and_two_seeds_differ():
    a, b, c = _gen(7).live(), _gen(7).live(), _gen(8).live()
    for t in ("customer", "orders", "lineitem"):
        assert all(np.array_equal(x, y) for x, y in zip(a[t], b[t]))
    assert not np.array_equal(a["orders"][1], c["orders"][1])
    assert not np.array_equal(a["customer"][1], c["customer"][1])
    # a seed past 32 signed bits is a seed like any other
    assert len(_gen(2**31 + 12345).live()["orders"][0]) == 1500


def test_refresh_is_rf1_plus_rf2_of_sf_times_1500_orders():
    g = Generator(sf=0.01, seed=3)
    g.snapshot()
    before = g.live()["orders"][0].copy()
    rows = g.refresh_rows()
    (o_cols, o_diffs), (l_cols, l_diffs) = rows["orders"], rows["lineitem"]
    assert (o_diffs == 1).sum() == 15 and (o_diffs == -1).sum() == 15
    assert set(o_cols[0][o_diffs == -1]) == set(before[:15])  # the oldest orders go
    live = g.live()
    assert len(live["orders"][0]) == 15_000
    assert np.isin(live["lineitem"][0], live["orders"][0]).all()
    assert set(l_cols[0][l_diffs == -1]) <= set(before[:15])


def test_q3_agrees_with_the_programs_oracle():
    from materialize_tpu.models import tpch as models_tpch

    live = _gen(11, sf=0.01).live()
    building = SEGMENTS.index("BUILDING")
    want = models_tpch.q3_oracle(live["customer"], live["orders"], live["lineitem"], building_code=building)
    want = {k: v for k, v in want.items() if v != 0}
    assert want and ref.q3(live) == want


def test_parser_and_differ():
    _reference, parse = ref.VIEWS["q3"]
    assert parse([("5", "12.3400", "100", "0")]) == {(5, 100, 0): 123400}
    with pytest.raises(ValueError):
        parse([("5", "12.34001", "100", "0")])
    with pytest.raises(ValueError):
        parse([("5", "1", "100", "0"), ("5", "2", "100", "0")])
    assert ref.differ({(1,): 2}, {(1,): 2, (2,): 3}) == 1 and ref.differ({(1,): 2, (3,): 1}, {(1,): 3}) == 2


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_float32_comes_out_not_correct(seed):
    """The control (the reference in float32 in the program's place) goes
    through the comparison a run makes and has to come out as not correct;
    at the cell's own size it was read at SF1 (PERF.md), here at SF0.01."""
    from chipbench import run as bench_run
    from chipbench.control import control

    bench = bench_run.load_json(bench_run.ROOT / "BENCHMARK.json")
    config = bench_run.load_json(bench_run.ROOT / bench["configs"][0]["file"])
    out = control(config, seed, refreshes=7, scale=0.01)
    assert out["correct"] is False
    failing = {k for k, c in out["checks"].items() if not bench_run.holds(c)}
    assert failing == {"subscribe_rows_differ", "pgwire_rows_differ", "http_rows_differ"}
    assert out["checks"]["subscribe_rows_differ"]["value"] > out["reference_rows"] // 2
