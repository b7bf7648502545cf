"""An ingested batch is built on the host at its bucket and transferred once
(ISSUE 31): no XLA program holds a row count in its shape, so a build asks
for none, and a refresh after the warm-ups asks for none either. Counts of
programs requested (`jax.monitoring`, conftest's `programs_built`), never a
wall clock."""

import json
from pathlib import Path

import numpy as np

import chipbench
from materialize_tpu.adapter import Coordinator
from materialize_tpu.repr import UpdateBatch

from test_repr import _builds  # (host, device) readings of mzt_batch_build_total


def _lineitems(n: int, tick: int) -> UpdateBatch:
    rng = np.random.default_rng(n)
    cols = (
        rng.integers(0, 1 << 40, n),
        rng.integers(0, 1 << 20, n).astype(np.int32),
        rng.random(n).astype(np.float32),
    )
    return UpdateBatch.build((), cols, np.full(n, tick), np.where(rng.random(n) < 0.5, 1, -1))


def test_builds_of_new_row_counts_in_a_bucket_request_no_program(programs_built):
    host, device = _builds()
    assert _lineitems(12_011, 1).cap == 16_384
    built = programs_built()
    for i, n in enumerate(range(11_800, 12_200, 10)):  # forty row counts, none met before
        b = _lineitems(n, 2 + i)
        assert b.cap == 16_384
    assert programs_built() == built
    assert int(b.count()) == n  # (a program of the bucket's shape, after the count above)
    assert _builds() == (host + 41, device)


def test_refreshes_after_the_second_request_no_program(programs_built):
    """Q3's text over `LOAD GENERATOR TPCH` through `Coordinator.advance()`:
    refresh 1 builds the heads' merges, refresh 2 those of the shared join
    traces (PERF.md section 4); from the third on a refresh's lineitem row
    count is new almost every time (some 360 in a bucket of 512 at this
    scale) and nothing is requested. Every batch came from the host."""
    config = json.loads((Path(chipbench.__file__).parent / "configs" / "loadgen_tpch_sf1_q3.json").read_text())
    _, device = _builds()
    c = Coordinator()
    for sql in config["setup_sql"]:
        c.execute(sql.format(scale_factor="0.03"))
    requested, lineitems = [], set()
    for _ in range(6):
        built, host = programs_built(), _builds()[0]
        c.advance()
        requested.append(programs_built() - built)
        assert _builds()[0] >= host + 2  # orders and lineitem, at least
        lineitems.add(len(c.generators[0][0].live()["lineitem"]["l_orderkey"]))
    assert requested[0] > 0 and requested[2:] == [0, 0, 0, 0], requested
    assert len(lineitems) > 3, "the refreshes' row counts did not differ: the test shows nothing"
    assert _builds()[1] == device
    assert c.execute("SELECT count(*) FROM q3").rows[0][0] > 0
